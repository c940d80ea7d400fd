"""Process start to the window's opening: imports, device start, weights,
calibration and quantization, engine build, warm-up, and whatever load the
cell needs before it measures (session histories, the closed loop's
ramp)."""


def read(ctx):
    return ctx.times["setup_s"]
