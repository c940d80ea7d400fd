"""Share of the traced window in which no operation ran on the device
(1 - the union of device-op intervals over the window)."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_ns <= 0 or t.busy_ns <= 0:
        return None
    return 100.0 * (1.0 - t.busy_ns / t.window_ns)
