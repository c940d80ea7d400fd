"""Mean wait from admission to the first prefill chunk of the requests
whose first chunk ran in the window (engine counters ``prefill_wait_s``
over ``prefill_started``)."""


def read(ctx):
    n = ctx.window_delta("prefill_started")
    return 1e3 * ctx.window_delta("prefill_wait_s") / n if n else None
