#!/usr/bin/env python3
"""Find the knee of an open-loop cell: one set-up, then one window at each
offered rate, in one process on the chip.

    python3 bench/tools/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 0.5,1,1.5,2

Prints, for each rate, the end-to-end numbers, the median queue wait and the
requests due in the window that had no first token when it closed. The knee
is the highest rate at which output tokens per second still follows the
offered load and the queue does not grow through the window.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    from bench import run as R
    from bench.drivers import open_loop as D
    from bench.lib import stats

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    spec = json.loads((R.BENCH / "configs" / f"{cell['config']}.json")
                      .read_text())
    mix = json.loads((R.BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        R.log(f"sweep: JAX finds no TPU (platform {dev.platform!r})")
        return 1
    R.setup_compile_cache()
    env = R.Env(dev, False, time.perf_counter())
    state = D.setup(env, spec, mix, args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        reqs, c = D.run_window(env, state, spec, mix, args.seed,
                               args.seconds, rate=rate)
        m = D.measure(spec, reqs, c, c["window_s"])
        unserved = sum(1 for tr in reqs
                       if 0 <= tr.plan.due < args.seconds and not tr.times)
        print(json.dumps({
            "rate_per_s": rate,
            "ttft_p90_ms": stats.percentile(m["ttft_s"], 90) * 1e3,
            "ttft_p50_ms": stats.median(m["ttft_s"]) * 1e3,
            "itl_p95_ms": stats.percentile(m["itl_s"], 95) * 1e3,
            "output_tok_per_s": m["out_tokens"] / c["window_s"],
            "queue_wait_ms": stats.median(m["queue_s"]) * 1e3,
            "due": len(m["ttft_s"]), "unserved_at_close": unserved,
            "busy_share": c["busy_s"] / c["window_s"],
            "decode_share": c["decode_s"] / c["window_s"],
            "compiles_in_window": c["compiles"],
        }), flush=True)
        state["engine"].run()   # drain before the next rate
    return 0


if __name__ == "__main__":
    sys.exit(main())
