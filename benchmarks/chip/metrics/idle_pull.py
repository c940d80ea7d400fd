"""Share of the traced window in which the device idled while the engine
pulled logits to the host (innermost span ``serve.*.pull``)."""
from chipbench import hostspans


def read(ctx):
    return hostspans.idle_share(ctx, "pull")
