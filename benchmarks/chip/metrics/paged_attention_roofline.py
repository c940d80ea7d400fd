"""The paged-attention kernel's share of its roofline in the traced window:
the least time for every decode call (one per layer per step, over the
live K/V blocks of the active slots, ``work.paged_attention``) over the
summed device time of the ``paged_attention`` events."""
from chipbench import work


def read(ctx):
    t = ctx.trace
    if t is None or not t.kernel_ns.get("paged_attention"):
        return None
    m = ctx.model
    total = work.Work()
    for s in ctx.steps:
        if s.contexts:
            one = work.paged_attention(s.contexts, m["n_heads"], m["n_kv"],
                                       m["head_dim"], ctx.block_size,
                                       ctx.kv_bytes)
            total = total + work.Work(one.flops * m["n_layers"],
                                      one.bytes * m["n_layers"])
    ctx.note("paged_attention bound",
             total.bound(ctx.peaks["bf16_flops"],
                         ctx.peaks["hbm_bytes_per_s"]))
    ideal = total.seconds(ctx.peaks["bf16_flops"],
                          ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * ideal / (t.kernel_ns["paged_attention"] / 1e9)
