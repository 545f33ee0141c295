"""Reduction of a profiler trace (XSpace) to the benchmark's device numbers.

Device planes are named ``/device:TPU:<n>``. On each, the line ``XLA Ops``
holds one event per operation that ran, and ``XLA Modules`` one event per
run of a compiled program. The host plane holds the harness's own spans
(``jax.profiler.TraceAnnotation``), among them ``bench_window``, which
bounds the measured window; every number here is clipped to it.

- busy: the union of the intervals of ``XLA Ops`` events, per device;
- idle gaps: the complements of busy inside the window, each labelled by
  the harness span that was open on the host at the gap's middle;
- top ops: device time by op name, each op counted by its self time (its
  duration less that of the ops nested in it, as a loop's body is);
- kernel launches: the ``dora_linear`` / ``dora_linear_gemv`` custom calls,
  with the extents read from the op's own text (output ``f32[M,N]``,
  operands ``x (M, K)``, codes, scale, ``A (K, r)``, ...), each attributed
  to the program run that contains it.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench_window"
HARNESS_SPANS = ("submit", "step", "arrival_wait", "calibrate")


@dataclasses.dataclass
class Event:
    name: str
    start: int          # ns
    end: int            # ns


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host_spans: List[Event]      # the harness's spans, by start
    window: Tuple[int, int]


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        start = int(e.start_ns)
        out.append(Event(e.name, start, start + int(e.duration_ns)))
    out.sort(key=lambda x: x.start)
    return out


def load(profile) -> Trace:
    """A ``jax.profiler.ProfileData`` reduced to what the metrics read."""
    devices, spans = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = _events(line)
                elif line.name == "XLA Modules":
                    modules = _events(line)
            devices.append(Device(plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in _events(line):
                    if ev.name == WINDOW_SPAN or ev.name in HARNESS_SPANS:
                        spans.append(ev)
    spans.sort(key=lambda x: x.start)
    wins = [s for s in spans if s.name == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    window = (wins[0].start, wins[0].end)
    return Trace(devices, [s for s in spans if s.name != WINDOW_SPAN],
                 window)


def union(intervals: Iterable[Tuple[int, int]], lo: int, hi: int):
    """Sorted, merged intervals clipped to [lo, hi]."""
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_seconds(tr: Trace, dev: Device) -> float:
    lo, hi = tr.window
    return sum(b - a for a, b in union(((e.start, e.end) for e in dev.ops),
                                       lo, hi)) / 1e9


def window_seconds(tr: Trace) -> float:
    return (tr.window[1] - tr.window[0]) / 1e9


def mean_busy_seconds(tr: Trace) -> float:
    """Busy seconds averaged over the traced devices."""
    if not tr.devices:
        raise ValueError("no device plane in the trace")
    return sum(busy_seconds(tr, d) for d in tr.devices) / len(tr.devices)


def _open_span(spans: Sequence[Event], t: int) -> str:
    starts = [s.start for s in spans]
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        if spans[i].start <= t < spans[i].end:
            return spans[i].name
        i -= 1
        if i >= 0 and t - spans[i].start > 60e9:
            break
    return "none"


def idle_gaps(tr: Trace, dev: Device, top: int = 10):
    """The longest idle gaps inside the window: ``[label, seconds]``,
    longest first, each labelled by the harness span open at its middle."""
    lo, hi = tr.window
    busy = union(((e.start, e.end) for e in dev.ops), lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return [[_open_span(tr.host_spans, (a + b) // 2), (b - a) / 1e9]
            for a, b in gaps[:top]]


def self_seconds(tr: Trace, dev: Device) -> Dict[str, float]:
    """Device seconds inside the window by op name, each op counted by its
    self time: its clipped duration less that of the ops nested in it."""
    lo, hi = tr.window
    total: Dict[str, float] = collections.defaultdict(float)
    stack: List[List] = []          # [end, name, clipped length]

    def close(item):
        total[item[1]] += item[2] / 1e9

    for e in sorted(dev.ops, key=lambda x: (x.start, -x.end)):
        while stack and stack[-1][0] <= e.start:
            close(stack.pop())
        length = max(0, min(e.end, hi) - max(e.start, lo))
        if stack:
            stack[-1][2] -= length
        stack.append([e.end, e.name, length])
    while stack:
        close(stack.pop())
    return dict(total)


def top_ops(tr: Trace, dev: Device, top: int = 10):
    """``[name, seconds]`` of the ops that took the most device time inside
    the window (self time, summed by name), most first."""
    total = self_seconds(tr, dev)
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])
            [:top] if v > 0]


_LAUNCH = re.compile(
    r"^%(dora_linear(?:_gemv)?)(?:\.\d+)? = (\w+)\[(\d+),(\d+)\]")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "s8": 1, "u8": 1, "f16": 2}


@dataclasses.dataclass
class Launch:
    kernel: str
    module: str
    seconds: float
    m: int
    k: int
    n: int
    r: int
    x_bytes: int
    out_bytes: int


def parse_launch(name: str):
    """``(kernel, m, k, n, r, x_bytes, out_bytes)`` of a dora_linear launch
    from its op text, or None for any other op."""
    head = _LAUNCH.match(name)
    if head is None or "custom-call(" not in name:
        return None
    args = name.split("custom-call(", 1)[1]
    shapes = [(t, [int(v) for v in d.split(",") if v])
              for t, d in _SHAPE.findall(args)]
    if len(shapes) < 5:
        return None
    (xt, (m, k)), (_, (k2, r)) = shapes[0], shapes[-3]
    return (head.group(1), m, k, int(head.group(4)), r, _BYTES[xt],
            _BYTES[head.group(2)])


_STAGED = re.compile(r"\w+\[[\d,]*\]\{[^}]*S\(1\)\} (%[\w.-]+)")


def _key(name: str) -> str:
    return name.split(" = ", 1)[0]


def launches(tr: Trace, dev: Device) -> List[Launch]:
    """Every kernel launch inside the window, with its program's name.

    A launch's time is its own plus that of the ops in the same program run
    that staged its operands into on-chip memory (operands whose layout
    carries ``S(1)``, produced by a ``dynamic-slice`` or ``copy`` op, as
    the per-layer slices of the stacked codes are): those ops are where the
    kernel's operands are read from HBM."""
    lo, hi = tr.window
    mods = [m for m in dev.modules if m.end > lo and m.start < hi]
    starts = [m.start for m in mods]
    last: Dict[Tuple[int, str], Event] = {}
    out = []
    for e in dev.ops:
        if e.end <= lo or e.start >= hi:
            continue
        i = bisect.bisect_right(starts, e.start) - 1
        run = i if i >= 0 and mods[i].end >= e.end else -1
        if not e.name.startswith("%dora_linear"):
            k = _key(e.name)
            if "dynamic-slice" in k or k.startswith("%copy"):
                last[(run, k)] = e
            continue
        p = parse_launch(e.name)
        if p is None:
            continue
        kernel, m, k, n, r, xb, ob = p
        secs = e.end - e.start
        args = e.name.split("custom-call(", 1)[1]
        for operand in set(_STAGED.findall(args)):
            src = last.get((run, operand))
            if src is not None:
                secs += src.end - src.start
        out.append(Launch(kernel, mods[run].name if run >= 0 else "none",
                          secs / 1e9, m, k, n, r, xb, ob))
    return out


def module_kinds(tr: Trace, dev: Device, n_layers: int, batch: int,
                 max_len: int) -> Dict[str, str]:
    """``{program name: "decode" | "prefill"}`` for the programs that hold a
    kernel launch, told apart by the batch of the layer-stacked cache their
    ops carry: ``[L, batch, max_len, ...]`` in the batched decode step and
    ``[L, 1, max_len, ...]`` in the batch-1 prefill chunk. (The programs
    have no names of their own to tell them apart by.)"""
    dec = f"[{n_layers},{batch},{max_len},"
    pre = f"[{n_layers},1,{max_len},"
    kinds: Dict[str, str] = {}
    for module, evs in ops_by_module(tr, dev, lambda e: True).items():
        if module == "none" or not any(
                e.name.startswith("%dora_linear") for e in evs):
            continue
        text = [e.name for e in evs]
        if any(dec in t for t in text):
            kinds[module] = "decode"
        elif any(pre in t for t in text):
            kinds[module] = "prefill"
    return kinds


def roofline_share(launches: Sequence[Launch], flops_peak: float,
                   bytes_per_s: float, cost) -> Optional[float]:
    """Least time the chip could take for these launches (the larger of
    operations over peak and bytes over bandwidth, per launch) over the
    time they took. ``cost(m, k, n, r, x_bytes, out_bytes)`` gives a
    launch's ``(operations, bytes)``."""
    took = sum(x.seconds for x in launches)
    if not launches or took <= 0:
        return None
    least = 0.0
    for x in launches:
        ops, nbytes = cost(x.m, x.k, x.n, x.r, x.x_bytes, x.out_bytes)
        least += max(ops / flops_peak, nbytes / bytes_per_s)
    return least / took


def ops_by_module(tr: Trace, dev: Device, match) -> Dict[str, List[Event]]:
    """Ops inside the window for which ``match(event)`` holds, grouped by the
    name of the program run that contains them."""
    lo, hi = tr.window
    mods = [m for m in dev.modules if m.end > lo and m.start < hi]
    starts = [m.start for m in mods]
    out: Dict[str, List[Event]] = collections.defaultdict(list)
    for e in dev.ops:
        if e.end <= lo or e.start >= hi or not match(e):
            continue
        i = bisect.bisect_right(starts, e.start) - 1
        name = mods[i].name if i >= 0 and mods[i].end >= e.end else "none"
        out[name].append(e)
    return dict(out)


def first_device(tr: Trace) -> Optional[Device]:
    return tr.devices[0] if tr.devices else None
