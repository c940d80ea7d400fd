"""Share of the traced window in which the device idled under any other
engine span: admission, copy on write, building inputs, dispatching a
program, and the own time of ``step``, ``prefill``, ``decode`` and
``spec``."""
from chipbench import hostspans


def read(ctx):
    return hostspans.idle_share(ctx, "dispatch")
