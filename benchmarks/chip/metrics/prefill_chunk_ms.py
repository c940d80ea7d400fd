"""Device time per execution of the prefill chunk's program
(``jit__chunk``) in the traced window (ms)."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.module_calls.get("jit__chunk"):
        return None
    return t.module_ns["jit__chunk"] / t.module_calls["jit__chunk"] / 1e6
