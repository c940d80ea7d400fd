"""Useful operations of the traced window's decode steps over their device
time at the chip's bf16 peak: each active slot's token through the
dense-equivalent linears (an MoE layer's router and its held experts'
expected share) and the ``lm_head``, and attention at its live context
(``work.decode_step_flops``)."""
from chipbench import work


def read(ctx):
    t = ctx.trace
    if t is None or not t.module_ns.get("jit__step"):
        return None
    flops = sum(work.decode_step_flops(ctx.model, s.contexts, ctx.experts)
                for s in ctx.steps if s.contexts)
    return 100.0 * flops / (t.module_ns["jit__step"] / 1e9
                            * ctx.peaks["bf16_flops"])
