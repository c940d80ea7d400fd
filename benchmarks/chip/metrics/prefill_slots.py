"""Mean share of the engine's slots held by admitted requests still
prefilling, over the decode steps of the window (engine counter
``prefill_slot_steps``); with ``occupancy`` it accounts for the held
slots."""


def read(ctx):
    steps = ctx.window_delta("decode_steps")
    if "prefill_slot_steps" not in ctx.win.stats1 or not steps:
        return None
    return 100.0 * ctx.window_delta("prefill_slot_steps") / (
        steps * ctx.slots)
