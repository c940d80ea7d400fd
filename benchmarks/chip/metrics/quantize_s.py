"""Wall time from the start of calibration to quantized parameters ready
on the device (host clock, synced)."""


def read(ctx):
    return ctx.times["quantize_s"]
