"""Pallas TPU kernel: flash-decoding attention over the paged KV block arena.

The serving decode path used to gather every request's K/V blocks into a
dense ``(B, MB*bs, KV, hd)`` copy before a pure-jnp softmax, so attention
bytes scaled with the block-table *width* even for short requests.  This
kernel reads arena blocks in place: the per-request block table is a
scalar-prefetch operand, so the K/V ``BlockSpec`` index maps chase it —
grid step ``(b, h, j)`` DMAs physical block ``block_table[b, j]`` of KV
head ``h`` straight from the arena into VMEM, and the ``(bq=W*G, bs)``
score tile, online-softmax stats, and output accumulator never leave VMEM.
The arena is laid out ``(N, KV, bs, hd)`` so that one block of one head is
a ``(bs, hd)`` tile in the last two dimensions, which is what a TPU block
copy addresses.

Layout choices (mirroring ``kernels/flash_attention/flash.py``):

  * GQA via index map: queries are regrouped to ``(B, KV, W*G, hd)`` so one
    grid step serves all G query heads sharing KV head ``h`` — the K/V
    arena is never expanded to H heads in HBM.
  * Ring/window masks are computed in-kernel from stored absolute
    positions (the ``paged_slot_positions`` semantics): slot ``s`` of a
    request with ``cnt`` inserted tokens and ring capacity ``cap`` holds
    position ``last - ((last - s) % cap)`` with ``last = cnt - 1``; a slot
    is a valid key for the query at ``qpos`` iff it was ever written
    (``stored >= 0`` and ``s < cap``), is causally visible
    (``stored <= qpos``), and sits inside the sliding window.
  * Never-written trailing blocks are skipped: ``nblk[b]`` (the number of
    logical blocks actually holding keys) gates the compute with
    ``pl.when``, and the index map clamps ``j`` to ``nblk[b] - 1`` so the
    skipped steps re-address the previous block and no fresh DMA is
    issued.
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


def _kernel(bt_ref, cnt_ref, ring_ref, nblk_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, bs: int, n_b: int, w: int, g: int,
            window: int | None, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < nblk_ref[b])
    def _block():
        q = q_ref[...].astype(jnp.float32) * scale          # (W*G, hd)
        k = k_ref[...].astype(jnp.float32)                  # (bs, hd)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        cnt = cnt_ref[b]                    # tokens inserted incl. last query
        cap = ring_ref[b]
        last = cnt - 1
        wg = w * g
        idx = j * bs + jax.lax.broadcasted_iota(jnp.int32, (wg, bs), 1)
        stored = last - ((last - idx) % cap)                # abs pos in slot
        qpos = (cnt - w
                + jax.lax.broadcasted_iota(jnp.int32, (wg, bs), 0) // g)
        mask = (idx < cap) & (stored >= 0) & (stored <= qpos)
        if window is not None:
            mask &= (qpos - stored) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                 # (W*G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # explicit re-mask: a fully-masked tile has s == m_new == NEG_INF,
        # where exp(s - m_new) = 1 would resurrect dead keys
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_b - 1)
    def _epilogue():
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
    scale = hd ** -0.5
    # (B, W, H, hd) -> (B, KV, W*G, hd), rows w-major within a KV group
    qr = q.reshape(b, w, kv, g, hd)
    qr = jnp.moveaxis(qr, 2, 1).reshape(b, kv, w * g, hd)
    cnt = jnp.maximum(pos.astype(jnp.int32), 1)
    ring = jnp.maximum(ring_cap.astype(jnp.int32), 1)
    # logical blocks actually holding keys; trailing blocks are skipped
    nblk = jnp.clip((jnp.minimum(cnt, ring) + bs - 1) // bs, 1, mb)

    def q_index(ib, ih, j, bt, c, r, nb):
        return (ib, ih, 0, 0)

    def kv_index(ib, ih, j, bt, c, r, nb):
        # clamp skipped steps to the last live block: the revisited index
        # elides the DMA, and pl.when skips the compute
        return (bt[ib, jnp.minimum(j, nb[ib] - 1)], ih, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, kv, mb),
        in_specs=[
            pl.BlockSpec((None, None, w * g, hd), q_index),
            pl.BlockSpec((None, None, bs, hd), kv_index),
            pl.BlockSpec((None, None, bs, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((None, None, w * g, hd), q_index),
        scratch_shapes=[
            pltpu.VMEM((w * g, 1), jnp.float32),    # running max
            pltpu.VMEM((w * g, 1), jnp.float32),    # running denom
            pltpu.VMEM((w * g, hd), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, n_b=mb, w=w, g=g, window=window,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, w * g, hd), q.dtype),
        interpret=interpret, name="paged_attention",
    )(block_table.astype(jnp.int32), cnt, ring, nblk, qr, k_arena, v_arena)
    out = out.reshape(b, kv, w, g, hd)
    return jnp.moveaxis(out, 2, 1).reshape(b, w, h, hd)
