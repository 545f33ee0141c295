"""The program's own spans in a profiler trace, beside the harness's.

The program opens ``jax.profiler.TraceAnnotation`` spans where its host
work happens (``PROGRAM_SPANS``): in the serving engine's tick and in
``Deployment.calibrate``. They land on the host plane of the same trace as
the device's ops, on the same clock, so they tell which host phase the
device waited on. ``bench/lib/trace.load`` keeps the harness's spans only;
``load`` here keeps the program's too.

- ``idle_in``: seconds of the window in which the device runs no op while
  a span of one name is open on the host (the measure of the
  intersection, whatever the spans' nesting);
- ``module_runs``: device seconds of each run of one compiled program;
- ``idle_gaps``: the trace's longest idle gaps, each labelled
  ``<harness span>/<innermost program span>`` at its middle (the harness
  span alone where no program span is open).
"""
from __future__ import annotations

import dataclasses
from typing import List

from bench.lib import trace as T

PROGRAM_SPANS = ("engine.admit", "engine.slot_write", "engine.decode",
                 "engine.sample", "calibrate.prepare", "calibrate.step")


@dataclasses.dataclass
class Trace(T.Trace):
    program_spans: List[T.Event] = dataclasses.field(default_factory=list)


def load(profile) -> Trace:
    """``trace.load`` of a ``jax.profiler.ProfileData``, with the
    program's spans (``PROGRAM_SPANS``) kept, by start."""
    spans = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in T._events(line)
                          if e.name in PROGRAM_SPANS]
    spans.sort(key=lambda x: x.start)
    base = T.load(profile)
    return Trace(base.devices, base.host_spans, base.window, spans)


def _idle(tr: T.Trace, dev: T.Device):
    """The idle intervals of ``dev`` inside the window, in order."""
    lo, hi = tr.window
    gaps, t = [], lo
    for a, b in T.union(((e.start, e.end) for e in dev.ops), lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def idle_in(tr: Trace, dev: T.Device, name: str) -> float:
    """Seconds of the window in which ``dev`` runs no op and a program
    span named ``name`` is open."""
    lo, hi = tr.window
    open_ = T.union(((s.start, s.end) for s in tr.program_spans
                     if s.name == name), lo, hi)
    idle = _idle(tr, dev)
    total, j = 0, 0
    for a, b in open_:
        while j < len(idle) and idle[j][1] <= a:
            j += 1
        k = j
        while k < len(idle) and idle[k][0] < b:
            total += min(b, idle[k][1]) - max(a, idle[k][0])
            k += 1
    return total / 1e9


def module_runs(tr: T.Trace, dev: T.Device, name: str) -> List[float]:
    """Seconds of each run of the program ``name`` (``XLA Modules`` events
    named ``name`` or ``name(<fingerprint>)``) inside the window,
    clipped to it."""
    lo, hi = tr.window
    out = []
    for m in dev.modules:
        if m.name.split("(", 1)[0] != name:
            continue
        a, b = max(m.start, lo), min(m.end, hi)
        if b > a:
            out.append((b - a) / 1e9)
    return out


def _innermost(spans: List[T.Event], t: int):
    """Name of the latest-started span open at ``t``, or None."""
    inner = None
    for s in spans:
        if s.start > t:
            break
        if t < s.end:
            inner = s.name
    return inner


def idle_gaps(tr: Trace, dev: T.Device, top: int = 10):
    """``trace.idle_gaps`` with each label ``<harness span>/<innermost
    program span>`` where a program span is open at the gap's middle."""
    gaps = sorted(_idle(tr, dev), key=lambda g: g[1] - g[0], reverse=True)
    out = []
    for a, b in gaps[:top]:
        mid = (a + b) // 2
        label = T._open_span(tr.host_spans, mid)
        inner = _innermost(tr.program_spans, mid)
        out.append([label if inner is None else f"{label}/{inner}",
                    (b - a) / 1e9])
    return out
