"""Median wait of the requests due in the window, from due time until the
engine gave the request a slot (a request still queued when the window
closed counts at its wait so far). Host clock, read after each engine
call."""
from bench.lib import stats


def read(ctx):
    waits = ctx["layer"].get("queue_s")
    if not waits:
        return None
    return stats.median(waits) * 1e3
