#!/usr/bin/env python3
"""Cut a recorded profiler trace down to a short slice that can be kept
with the benchmark's tests.

    python3 bench/tools/slice_trace.py <trace.xplane.pb> <out.txt.gz> \
        --around <program name> --ms 40

Keeps, from the first device plane, the ``XLA Modules`` and ``XLA Ops``
events that overlap a slice of ``--ms`` milliseconds starting 5 ms
before the first run of ``--around`` that has a full slice after it, and
from the host the harness's spans that overlap it; writes them as an XSpace text
proto (gzip) whose ``bench_window`` span is the slice.
"""
from __future__ import annotations

import argparse
import gzip
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]


def _quote(s: str) -> str:
    return json.dumps(s)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--around", required=True)
    ap.add_argument("--ms", type=float, default=40.0)
    args = ap.parse_args(argv)

    import jax

    from bench.lib.trace import HARNESS_SPANS, WINDOW_SPAN

    prof = jax.profiler.ProfileData.from_file(args.trace)
    dev = next(p for p in prof.planes if p.name.startswith("/device:TPU:"))
    lines = {l.name: list(l.events) for l in dev.lines}
    runs = [e for e in lines["XLA Modules"] if e.name == args.around]
    end_all = max(e.start_ns + e.duration_ns for e in lines["XLA Ops"])
    dur = int(args.ms * 1e6)
    lo = next(int(e.start_ns) - 5_000_000 for e in runs
              if e.start_ns - 5e6 + dur < end_all)
    hi = lo + dur
    base = lo - 1_000_000_000       # line origin before any kept event
    inside = lambda e: e.start_ns < hi and e.start_ns + e.duration_ns > lo

    def plane(pid, name, named_lines):
        meta, parts = {}, []
        for lid, (lname, evs) in enumerate(named_lines, 1):
            body = []
            for e in evs:
                mid = meta.setdefault(e.name, len(meta) + 1)
                body.append(
                    f"events {{ metadata_id: {mid} "
                    f"offset_ps: {int(round((e.start_ns - base) * 1000))} "
                    f"duration_ps: {int(round(e.duration_ns * 1000))} }}")
            parts.append(f"lines {{ id: {lid} name: {_quote(lname)} "
                         f"timestamp_ns: {base} {' '.join(body)} }}")
        md = " ".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                      f"name: {_quote(n)} }} }}" for n, i in meta.items())
        return (f"planes {{ id: {pid} name: {_quote(name)} "
                f"{' '.join(parts)} {md} }}")

    host = []
    for p in prof.planes:
        if p.name.startswith("/host:"):
            for l in p.lines:
                host += [e for e in l.events
                         if e.name in HARNESS_SPANS and inside(e)]

    class Win:
        name, start_ns, duration_ns = WINDOW_SPAN, lo, dur

    text = "\n".join([
        plane(1, dev.name, [(n, [e for e in lines[n] if inside(e)])
                            for n in ("XLA Modules", "XLA Ops")]),
        plane(2, "/host:CPU", [("python3", [Win] + host)]),
    ])
    with gzip.open(args.out, "wt") as f:
        f.write(text + "\n")
    print(f"slice [{lo}, {hi}) ns, {len(text)} characters")
    return 0


if __name__ == "__main__":
    sys.exit(main())
