"""95th percentile of every gap between consecutive tokens of every
request, over the gaps that end inside the window (ms)."""
from chipbench.traffic import percentile


def read(ctx):
    gaps = ctx.token_gaps()
    return 1e3 * percentile(gaps, 95) if gaps else None
