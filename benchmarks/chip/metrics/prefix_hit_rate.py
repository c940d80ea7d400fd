"""Share of the prompt tokens admitted in the window whose prefill the
prefix cache skipped (engine counters, window deltas)."""


def read(ctx):
    prompt = ctx.window_delta("prompt_tokens")
    if not prompt:
        return None
    return 100.0 * ctx.window_delta("prefill_tokens_saved") / prompt
