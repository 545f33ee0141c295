"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, from the profiler
trace (``bench/lib/trace.py``)."""
from bench.lib import trace as T


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr.devices:
        return None
    return 100.0 * (1.0 - T.mean_busy_seconds(tr) / T.window_seconds(tr))
