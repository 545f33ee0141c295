"""Median device time of one run of the batched decode program
(``jit_decode_step``, an ``XLA Modules`` event in the device trace) inside
the traced window. A program that does not carry that name reads
nothing."""
from bench.lib import spans
from bench.lib import trace as T
from bench.lib import stats


def read(ctx):
    tr = ctx.get("trace")
    dev = T.first_device(tr) if tr is not None else None
    if dev is None:
        return None
    runs = spans.module_runs(tr, dev, "jit_decode_step")
    return stats.median(runs) * 1e3 if runs else None
