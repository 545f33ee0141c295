#!/usr/bin/env python3
"""Readings of a cell's correctness check over many seeds, in one process
on the chip, for setting its limits (``bench/limits/<cell>.json``).

    python3 bench/tools/readings.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 4,5,6 --seconds 10

For every seed it runs the cell's timed path as a run does and prints the
numbers the check compares (the lower reading is their largest). For every
control seed it does the same with the control in the program's place and
prints those numbers (the upper reading is their smallest). The control is
the configuration's next precision down: for a served cell the program's own
int8 path (``Deployment.serve(accum="int8")``); for a calibration cell the
plain reference computed with int8 matmul operands, and besides it the
fault of a step that takes the mean over half of the batch (planted in the
reference put in the program's place). A served cell also
prints, on every seed, the gap of the token that the int8 reference puts
first at each served position.
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
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]

    from bench import run as R

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    spec = json.loads((R.BENCH / "configs" / f"{cell['config']}.json")
                      .read_text())
    mix = json.loads((R.BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        R.log(f"readings: JAX finds no TPU (platform {dev.platform!r})")
        return 1
    R.setup_compile_cache()
    env = R.Env(dev, False, time.perf_counter())
    if mix["kind"] == "open_loop":
        from bench.drivers import open_loop as D

        for seed, control in ([(s, False) for s in seeds]
                              + [(s, True) for s in controls]):
            state = D.setup(env, spec, mix, seed,
                            accum="int8" if control else "f32")
            reqs, c = D.run_window(env, state, spec, mix, seed, args.seconds)
            picked = D.sample_for_check(reqs, seed, mix["check_tokens"])
            w, a = state["w"], state["a"]
            del state, reqs
            length = mix["engine"]["max_len"]
            got = D.check(spec, w, a, picked, length)
            row = {"seed": seed, "control": "program_int8" if control
                   else None, **got}
            if not control:
                low = D.check(spec, w, a, picked, length, lowp="int8")
                row["int8_reference_max_gap"] = low["max_gap"]
                row["int8_reference_mean_gap"] = low["mean_gap"]
            print(json.dumps(row), flush=True)
            del w, a, picked
    else:
        from bench.drivers import calibrate as D

        for seed in seeds + [s for s in controls if s not in seeds]:
            state = D.setup(env, spec, mix, seed)
            del state["dep"]
            if seed in seeds:
                got = D.check(spec, state)
                print(json.dumps({"seed": seed, "control": None, **got}),
                      flush=True)
            if seed in controls:
                got = D.check(spec, state, lowp="int8")
                print(json.dumps({"seed": seed, "control": "reference_int8",
                                  **got}), flush=True)
                got = D.check(spec, state, fault="half_batch")
                print(json.dumps({"seed": seed, "control": "half_batch",
                                  **got}), flush=True)
            del state
    return 0


if __name__ == "__main__":
    sys.exit(main())
