#!/usr/bin/env python3
"""Run one cell through ``bench/run.py``'s entry point with ``--trace 1``,
and read the program's own spans (``bench/lib/spans.py``), which the
harness's reduction does not keep.

    python3 bench/tools/spans.py --workload <cell> --seed <n> --seconds <s> \
        [--profile 0]

The harness checks the cell, the device and its peaks and prints the one
result line, which holds the cell's end-to-end numbers; the traced slice
(an open-loop cell's last ``trace_s`` of the window, or one calibration
call after it) with its host-clock length, the engine's counters in it, and
the mean length of each harness span in it and before it; and, with the
profiler on, every per-layer metric the cell reports, the share of the
slice in which the device idles under each program span, the share in
which each program span is open, the median device time of each named
program, and the idle gaps labelled ``<harness span>/<program span>``.
``--profile 0`` times the same slice with no profiler session, which
gives what the profiler costs.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import importlib
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as R  # noqa: E402

PROGRAMS = ("jit_decode_step", "jit_prefill_chunk", "jit_calib_step")


class SliceEnv(R.Env):
    """The harness's ``Env`` with its spans timed on the host clock, and
    the traced slice timed with or without the profiler."""

    def __init__(self, device, trace: bool, t0: float, *, profile: bool):
        super().__init__(device, trace, t0)
        self.profile = profile
        self.timed = []         # (harness span, start, seconds)
        self.slice = None       # [start, end], host clock

    @contextlib.contextmanager
    def span(self, name):
        t = time.perf_counter()
        with super().span(name):
            yield
        self.timed.append((name, t, time.perf_counter() - t))

    def trace_start(self):
        self.slice = [time.perf_counter(), None]
        if self.profile:
            super().trace_start()

    def trace_stop(self):
        """``Env.trace_stop``, reducing the trace with ``spans.load``."""
        if self.slice is None or self.slice[1] is not None:
            return
        self.slice[1] = time.perf_counter()
        if not self.profile:
            return
        import jax

        from bench.lib import spans as S

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            files = sorted(Path(self._dir).rglob("*.xplane.pb"))
            if not files:
                raise RuntimeError("the profiler wrote no trace")
            self.trace = S.load(
                jax.profiler.ProfileData.from_file(str(files[-1])))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


def _span_ms(timed, start):
    """``{harness span: [mean ms before the slice, mean ms in it, n
    before, n in]}``."""
    out = {}
    for name in sorted({n for n, _, _ in timed}):
        before = [d for n, t, d in timed if n == name and t < start]
        inside = [d for n, t, d in timed if n == name and t >= start]
        out[name] = [sum(xs) / len(xs) * 1e3 if xs else None
                     for xs in (before, inside)] + [len(before), len(inside)]
    return out


def _program(tr):
    from bench.lib import spans as S
    from bench.lib import stats
    from bench.lib import trace as T

    dev = T.first_device(tr)
    lo, hi = tr.window
    W = T.window_seconds(tr)
    opened = {n: sum(b - a for a, b in T.union(
        ((s.start, s.end) for s in tr.program_spans if s.name == n), lo, hi))
        / 1e9 for n in S.PROGRAM_SPANS}
    runs = {p: S.module_runs(tr, dev, p) for p in PROGRAMS}
    return {
        "window_s": W, "busy_s": T.mean_busy_seconds(tr),
        "idle_in_pct": {n: 100.0 * S.idle_in(tr, dev, n) / W
                        for n in S.PROGRAM_SPANS},
        "open_pct": {n: 100.0 * v / W for n, v in opened.items()},
        "spans": {n: sum(1 for s in tr.program_spans if s.name == n
                         and s.end > lo and s.start < hi)
                  for n in S.PROGRAM_SPANS},
        "module_ms": {p: [stats.median(r) * 1e3, len(r)] if r else None
                      for p, r in runs.items()},
        "idle_gaps": S.idle_gaps(tr, dev),
    }


def run_cell(env, bench, workload, spec, mix, limits, peak, seed, seconds):
    """``run.run_cell`` for a slice run: the end-to-end numbers, the
    traced slice's host-clock readings and, profiled, the per-layer
    metrics and the program-span readings."""
    driver = importlib.import_module(f"bench.drivers.{mix['kind']}")
    res = driver.run(env, spec, mix, seed, seconds)
    correct, checks = R.judge(res["readings"], limits)
    a, b = env.slice
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"], "profile": int(env.profile),
           "end_to_end": dict(res["end_to_end"], setup_s=env.setup_s),
           "slice": {"seconds": b - a,
                     "counters": res["layer"].get("in_trace") or {},
                     "span_ms": _span_ms(env.timed, a)},
           "trace_device": {}}
    if env.profile:
        from bench.lib import trace as T

        ctx = {"spec": spec, "mix": mix, "layer": res["layer"],
               "trace": env.trace, "peaks": peak}
        out["metrics"] = {
            m["name"]: R.load_reader(m["name"])(ctx)
            for m in R.cell_metrics(bench, workload, "per_layer")}
        out["program"] = _program(env.trace)
        out["trace_device"] = {"busy_s": T.mean_busy_seconds(env.trace),
                               "window_s": T.window_seconds(env.trace)}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    # the harness's own entry point, with its checks of the cell, the
    # device, the chips and the peaks, its compile cache and its result
    # line; only the environment and the cell's reduction are the tool's
    R.Env = functools.partial(SliceEnv, profile=bool(args.profile))
    R.run_cell = run_cell
    return R.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
