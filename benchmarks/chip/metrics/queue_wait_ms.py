"""Mean wait from arrival to admission of the requests first admitted in
the window (engine counters ``queue_wait_s`` over ``admitted``)."""


def read(ctx):
    n = ctx.window_delta("admitted")
    return 1e3 * ctx.window_delta("queue_wait_s") / n if n else None
