"""Share of the traced window in which the device idled while the engine
sampled and recorded tokens on the host (innermost span
``serve.*.sample``)."""
from chipbench import hostspans


def read(ctx):
    return hostspans.idle_share(ctx, "sample")
