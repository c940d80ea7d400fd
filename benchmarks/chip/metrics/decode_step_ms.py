"""Device time per execution of the decode step's program (``jit__step``)
in the traced window (ms)."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.module_calls.get("jit__step"):
        return None
    return t.module_ns["jit__step"] / t.module_calls["jit__step"] / 1e6
