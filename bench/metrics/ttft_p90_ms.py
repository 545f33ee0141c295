"""90th percentile over every request due in the window of due time ->
first token, host clock (a request with no first token when the window
closed counts at its wait so far)."""
from bench.lib import stats


def read(ctx):
    ttft = ctx["layer"].get("ttft_s")
    if not ttft:
        return None
    return stats.percentile(ttft, 90) * 1e3
