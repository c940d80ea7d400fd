"""Share of the traced window in which the device idled under no engine
span: the harness's load generator and record, Python between engine
calls."""
from chipbench import hostspans


def read(ctx):
    return hostspans.idle_share(ctx, "outside")
