"""Output tokens emitted inside the window over the window's seconds."""


def read(ctx):
    return ctx.tokens_in_window() / ctx.window_s
