"""Pallas TPU kernel: flash-decoding attention over the paged KV block arena.

The kernel reads each request's K/V blocks in place from the arena, which
stays in HBM (``memory_space=pl.ANY``), and never builds a dense copy of
the context.  The grid is one step per slot: step ``b`` serves every KV
head of request ``b`` and walks only its live blocks.

  * Page DMAs.  The arena is laid out ``(N, KV, bs, hd)``, so one page of
    every head, ``arena[block_table[b, j]]``, is one contiguous copy.  The
    step copies the live pages in compute blocks of ``P`` pages into a
    ``(2, P, KV, bs, hd)`` VMEM buffer per arena, with one
    ``make_async_copy`` per page for K and one for V.
  * Double buffering, across slots too.  The copies of the next compute
    block start before the step waits for block ``c`` and computes on it,
    so its bytes move while this block's scores and softmax run.  After a
    slot's last block the next block is the next slot's first, so the
    grid runs in order (``arbitrary``) and the buffer of a block is the
    parity of its index over the whole call: ``first[b]``, the compute
    blocks of the slots before ``b``, is a scalar-prefetch operand.
  * Live pages only.  ``nblk[b] = ceil(min(cnt, ring_cap) / bs)`` (the
    logical blocks holding keys) is a scalar-prefetch operand beside the
    block table: the step runs ``ceil(nblk / P)`` compute blocks in a
    ``fori_loop``, and a partial last block copies only its live pages.
    The pages past ``nblk`` keep whatever the buffer held; their keys fail
    the masks below and their values are zeroed before the ``p @ v`` dot.
    An inactive slot costs one grid step and one page.
  * ``P`` from the shapes.  ``pages_per_block`` takes the largest
    ``P <= MB`` whose two buffers of K and V fit ``VMEM_BUDGET``, so a bf16
    arena gets twice the pages of an f32 one, and a tensor-parallel shard
    (fewer KV heads per page) more again.  Nothing else chooses it.
  * No split over the context.  A v5e chip has one TensorCore, and the
    slots already give the grid one independent step each; a split would
    also need a combine outside this kernel.

Per compute block and KV head ``h``, the ``(W*G, P*bs)`` score tile, the
online-softmax statistics and the output accumulator stay in VMEM:

  * GQA: queries are regrouped to ``(B, KV, W*G, hd)``, so the ``G`` query
    heads that share KV head ``h`` are the rows of one tile; K/V is never
    expanded to ``H`` heads.
  * Ring/window masks are computed in-kernel from stored absolute
    positions (the ``paged_slot_positions`` semantics): slot ``s`` of a
    request with ``cnt`` inserted tokens and ring capacity ``cap`` holds
    position ``last - ((last - s) % cap)`` with ``last = cnt - 1``; a slot
    is a valid key for the query at ``qpos`` iff it was ever written
    (``stored >= 0`` and ``s < cap``), is causally visible
    (``stored <= qpos``), and sits inside the sliding window.
  * ``W >= 1`` queries per request ride the same kernel: decode is W=1,
    the speculative draft catch-up W=2, and target verify W=k+1.  Rows of
    the ``W*G`` query slab are ordered w-major, so row ``r`` is query
    position ``cnt - W + r // G``.

``interpret=True`` (asked for by tests and forced paths only) runs the
identical kernel through the Pallas interpreter, so CPU CI exercises the
real kernel semantics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# VMEM for the K and V page buffers, two compute blocks each
VMEM_BUDGET = 4 * 2**20


def pages_per_block(mb: int, kv: int, bs: int, hd: int, dtype) -> int:
    """The largest P <= MB whose ``2 (buffers) x 2 (K, V) x P`` pages of
    ``(kv, bs, hd)`` fit ``VMEM_BUDGET``; at least one page."""
    page = kv * bs * hd * jnp.dtype(dtype).itemsize
    return max(1, min(mb, VMEM_BUDGET // (4 * page)))


def _kernel(bt_ref, cnt_ref, ring_ref, nblk_ref, first_ref, q_ref, k_hbm,
            v_hbm, o_ref, k_buf, v_buf, sems, m_ref, l_ref, acc_ref, *,
            bs: int, pages: int, w: int, g: int, window: int | None,
            scale: float):
    b = pl.program_id(0)
    kv = k_buf.shape[2]
    hd = k_buf.shape[4]
    wg = w * g
    tk = pages * bs
    nblk = nblk_ref[b]
    n_c = (nblk + pages - 1) // pages

    def page_copies(sb, c, buf, op):
        """Start (or wait for) the copies of the live pages of slot
        ``sb``'s compute block ``c`` into buffer ``buf``."""
        def one(p, carry):
            page = bt_ref[sb, c * pages + p]
            for i, (src, dst) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                copy = pltpu.make_async_copy(src.at[page], dst.at[buf, p],
                                             sems.at[i, buf])
                getattr(copy, op)()
            return carry
        jax.lax.fori_loop(0, jnp.minimum(pages, nblk_ref[sb] - c * pages),
                          one, 0)

    @pl.when(b == 0)
    def _first():
        page_copies(0, 0, 0, "start")

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    cnt = cnt_ref[b]                        # tokens inserted incl. last query
    cap = ring_ref[b]
    last = cnt - 1
    qpos = cnt - w + jax.lax.broadcasted_iota(jnp.int32, (wg, tk), 0) // g

    def block(c, carry):
        gi = first_ref[b] + c
        buf = gi % 2

        @pl.when(c + 1 < n_c)
        def _next_block():
            page_copies(b, c + 1, 1 - buf, "start")

        @pl.when((c + 1 == n_c) & (b + 1 < pl.num_programs(0)))
        def _next_slot():
            page_copies(b + 1, 0, 1 - buf, "start")

        page_copies(b, c, buf, "wait")
        idx = c * tk + jax.lax.broadcasted_iota(jnp.int32, (wg, tk), 1)
        stored = last - ((last - idx) % cap)                # abs pos in slot
        mask = (idx < cap) & (stored >= 0) & (stored <= qpos)
        if window is not None:
            mask &= (qpos - stored) < window
        fetched = (jax.lax.broadcasted_iota(jnp.int32, (tk, 1), 0)
                   < (nblk - c * pages) * bs)
        for h in range(kv):
            q = q_ref[h].astype(jnp.float32) * scale        # (W*G, hd)
            k = k_buf[buf, :, h].reshape(tk, hd).astype(jnp.float32)
            v = v_buf[buf, :, h].reshape(tk, hd).astype(jnp.float32)
            v = jnp.where(fetched, v, 0.0)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[h]                               # (W*G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # explicit re-mask: a fully-masked tile has s == m_new ==
            # NEG_INF, where exp(s - m_new) = 1 would resurrect dead keys
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        return carry

    jax.lax.fori_loop(0, n_c, block, 0)
    o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                  ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention_pallas(q: jax.Array, k_arena: jax.Array,
                           v_arena: jax.Array, block_table: jax.Array,
                           pos: jax.Array, ring_cap: jax.Array, *,
                           window: int | None = None,
                           interpret: bool = False) -> jax.Array:
    """q (B, W, H, hd); arenas (N, KV, bs, hd); block_table (B, MB);
    pos (B,) tokens inserted including the last query (queries sit at
    absolute positions pos-W .. pos-1, and their K/V must already be in the
    arena); ring_cap (B,) per-request ring capacity -> (B, W, H, hd)."""
    b, w, h, hd = q.shape
    _, kv, bs, _ = k_arena.shape
    g = h // kv
    mb = block_table.shape[1]
    pages = pages_per_block(mb, kv, bs, hd, k_arena.dtype)
    # (B, W, H, hd) -> (B, KV, W*G, hd), rows w-major within a KV group
    qr = q.reshape(b, w, kv, g, hd)
    qr = jnp.moveaxis(qr, 2, 1).reshape(b, kv, w * g, hd)
    cnt = jnp.maximum(pos.astype(jnp.int32), 1)
    ring = jnp.maximum(ring_cap.astype(jnp.int32), 1)
    # logical blocks actually holding keys; only these are copied
    nblk = jnp.clip((jnp.minimum(cnt, ring) + bs - 1) // bs, 1, mb)
    n_c = (nblk + pages - 1) // pages
    first = jnp.cumsum(n_c) - n_c       # compute blocks of the slots before

    slot_spec = pl.BlockSpec((None, kv, w * g, hd),
                             lambda ib, *_: (ib, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b,),
        in_specs=[slot_spec, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=slot_spec,
        scratch_shapes=[
            pltpu.VMEM((2, pages, kv, bs, hd), k_arena.dtype),
            pltpu.VMEM((2, pages, kv, bs, hd), v_arena.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),        # (K/V, buffer)
            pltpu.VMEM((kv, w * g, 1), jnp.float32),    # running max
            pltpu.VMEM((kv, w * g, 1), jnp.float32),    # running denom
            pltpu.VMEM((kv, w * g, hd), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, pages=pages, w=w, g=g,
                          window=window, scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, w * g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="paged_attention",
    )(block_table.astype(jnp.int32), cnt, ring, nblk, first, qr, k_arena,
      v_arena)
    out = out.reshape(b, kv, w, g, hd)
    return jnp.moveaxis(out, 2, 1).reshape(b, w, h, hd)
