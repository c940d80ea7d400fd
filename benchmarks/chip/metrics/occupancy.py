"""Mean share of the engine's slots that held a decoding request, over the
decode steps of the window (engine counter ``stats["occupancy"]``)."""


def read(ctx):
    occ = ctx.window_list("occupancy")
    return 100.0 * sum(occ) / len(occ) if occ else None
