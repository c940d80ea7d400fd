"""Median time to first token of the requests due inside the window, from
when each was due (ms).  A request with no first token when the window
closed counts as later than every one that had one."""
from chipbench.traffic import percentile


def read(ctx):
    seen, missing = ctx.ttfts()
    if not seen and not missing:
        return None
    return 1e3 * percentile(seen, 50, missing)
