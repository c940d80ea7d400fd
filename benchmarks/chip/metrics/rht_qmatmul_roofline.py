"""The fused RHT+qmatmul kernel's share of its roofline in the traced
window: the least time the chip could take for every call (the larger of
operations over the bf16 peak and bytes over HBM bandwidth, from
``work.rht_qmatmul`` at each matrix's allocated bits) over the summed
device time of the ``rht_qmatmul`` events.  Decode calls run on every
slot's row; a prefill chunk's on its tokens; an MoE layer's held experts
on their expected share of those rows (``QMatrix.share``)."""
from chipbench import work


def read(ctx):
    t = ctx.trace
    if t is None or not t.kernel_ns.get("rht_qmatmul"):
        return None
    total = work.Work()
    for s in ctx.steps:
        for q in ctx.qmatrices:
            if s.contexts:
                total = total + work.rht_qmatmul(ctx.slots, q)
            if s.chunk:
                total = total + work.rht_qmatmul(s.chunk, q)
    ctx.note("rht_qmatmul bound",
             total.bound(ctx.peaks["bf16_flops"],
                         ctx.peaks["hbm_bytes_per_s"]))
    ideal = total.seconds(ctx.peaks["bf16_flops"],
                          ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * ideal / (t.kernel_ns["rht_qmatmul"] / 1e9)
