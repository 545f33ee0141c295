"""95th percentile of every gap between successive output tokens whose
later token fell in the window, host clock."""
from bench.lib import stats


def read(ctx):
    itl = ctx["layer"].get("itl_s")
    if not itl:
        return None
    return stats.percentile(itl, 95) * 1e3
