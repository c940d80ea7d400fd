"""Wall time of the calibration pass alone (host clock; its statistics
come back as host arrays, so it is synced)."""


def read(ctx):
    return ctx.times["calibrate_s"]
