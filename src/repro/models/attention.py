"""Attention: chunked (flash-style) GQA with causal/sliding windows, KV caches,
and DeepSeek-V2 MLA (compressed-KV) — pure JAX, SPMD-friendly.

The chunked kernel is an online-softmax scan over KV blocks (queries chunked
too), so the S x S score matrix is never materialized: prefill_32k fits, and
under GSPMD a sequence-sharded cache turns the softmax reductions into
all-reduces (flash-decoding style partial-softmax combine, inserted by XLA).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ------------------------------------------------------------ chunked flash


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    q_offset: int | jax.Array = 0,
                    q_chunk: int = 1024, k_chunk: int = 1024,
                    remat_chunks: bool = False,
                    expand_kv: bool = False) -> jax.Array:
    """q (B,Sq,H,hd), k/v (B,Sk,KV,hd), H = KV*G -> (B,Sq,H,hd).

    ``window``: causal sliding window (attend to the last ``window`` keys).
    ``q_offset``: absolute position of q[0] (prefill continuation / decode).
    ``remat_chunks``: checkpoint the KV-chunk step so autodiff recomputes the
    (cq, ck) probability block in the backward instead of stacking it as a
    scan residual — the FlashAttention backward strategy; turns O(S^2)
    residual HBM traffic into O(S^2) recompute flops (EXPERIMENTS.md §Perf).
    """
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    if expand_kv and kv != h:
        # GQA reshape (h -> kv x g) defeats head sharding when h % mesh != 0
        # on the grouped layout; expanding K/V to h heads costs g x K/V bytes
        # but keeps the einsums shardable on the flat head axis (§Perf B2)
        g_rep = h // kv
        k = jnp.repeat(k, g_rep, axis=2)
        v = jnp.repeat(v, g_rep, axis=2)
        kv = h
    from repro.runtime.actsharding import shard_named
    q = shard_named(q, "qkv")
    k = shard_named(k, "qkv")
    v = shard_named(v, "qkv")
    dv = v.shape[-1]                # may differ from hd (MLA)
    g = h // kv
    scale = hd ** -0.5
    cq, ck = min(q_chunk, sq), min(k_chunk, sk)
    nq, nk = -(-sq // cq), -(-sk // ck)
    sq_p, sk_p = nq * cq, nk * ck
    qp = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
    qp = qp.reshape(b, nq, cq, kv, g, hd)
    kp = kp.reshape(b, nk, ck, kv, hd)
    vp = vp.reshape(b, nk, ck, kv, dv)
    qpos0 = jnp.asarray(q_offset, jnp.int32)

    def q_block(qi):
        qc = qp[:, qi].astype(jnp.float32) * scale          # (b,cq,kv,g,hd)
        qpos = qpos0 + qi * cq + jnp.arange(cq, dtype=jnp.int32)

        def kv_step(carry, ki):
            m, l, acc = carry
            kc = kp[:, ki].astype(jnp.float32)              # (b,ck,kv,hd)
            vc = vp[:, ki].astype(jnp.float32)
            kpos = ki * ck + jnp.arange(ck, dtype=jnp.int32)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qc, kc)     # (b,kv,g,cq,ck)
            mask = kpos[None, :] <= (qpos[:, None] if causal else jnp.int32(2**30))
            mask &= kpos[None, :] < sk                       # key padding
            if window is not None:
                mask &= (qpos[:, None] - kpos[None, :]) < window
            s = jnp.where(mask[None, None, None, :, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, vc)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kv, g, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kv, g, cq), jnp.float32)
        a0 = jnp.zeros((b, kv, g, cq, dv), jnp.float32)
        step = jax.checkpoint(kv_step) if remat_chunks else kv_step
        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                      jnp.arange(nk, dtype=jnp.int32))
        out = acc / jnp.maximum(l, 1e-30)[..., None]        # (b,kv,g,cq,hd)
        return jnp.moveaxis(out, 3, 1)                      # (b,cq,kv,g,hd)

    out = jax.lax.map(q_block, jnp.arange(nq, dtype=jnp.int32))  # (nq,b,cq,kv,g,dv)
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq_p, kv, g, dv)[:, :sq]
    return out.reshape(b, sq, h, dv).astype(q.dtype)


# ------------------------------------------------------------------ caches


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """Ring-buffered when capacity < full context (sliding-window archs)."""
    k: jax.Array          # (B, cap, KV, hd)
    v: jax.Array          # (B, cap, KV, hd)

    @staticmethod
    def init(b: int, cap: int, kv: int, hd: int, dtype=jnp.float32) -> "KVCache":
        return KVCache(k=jnp.zeros((b, cap, kv, hd), dtype),
                       v=jnp.zeros((b, cap, kv, hd), dtype))


def cache_insert(cache: KVCache, k_new: jax.Array, v_new: jax.Array,
                 pos: jax.Array) -> KVCache:
    """Insert one step (B,1,KV,hd) at ring slot pos % cap."""
    cap = cache.k.shape[1]
    slot = (pos % cap).astype(jnp.int32)
    k = jax.lax.dynamic_update_slice(cache.k, k_new.astype(cache.k.dtype),
                                     (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache.v, v_new.astype(cache.v.dtype),
                                     (0, slot, 0, 0))
    return KVCache(k=k, v=v)


# ------------------------------------------------------------ paged caches
#
# The serving engine (repro/serve) stores K/V in a fixed arena of
# (num_blocks, KV, block_size, hd) blocks shared by all requests (one block
# of one KV head is a (block_size, hd) tile, the unit the paged-attention
# kernel copies into VMEM); each request
# owns a row of a block table mapping logical block -> physical block.
# Logical token index for a request at absolute position p is p % ring_cap,
# where ring_cap = allocated_blocks * block_size: full-context requests get
# ring_cap >= total length (the ring never wraps, indices are linear), and
# sliding-window requests get ring_cap = ceil(window / block_size) * block_size
# so old blocks are reused in place (ring-window reuse).  Physical block 0 is
# reserved as the null block: unallocated table entries and writes from
# inactive slots land there and are never read as valid.


def paged_gather_kv(arena: jax.Array, block_table: jax.Array) -> jax.Array:
    """arena (N, KV, bs, hd), block_table (B, MB) int32 -> (B, MB*bs, KV, hd)."""
    g = arena[block_table]                       # (B, MB, KV, bs, hd)
    b, mb, kv, bs, hd = g.shape
    return jnp.swapaxes(g, 2, 3).reshape(b, mb * bs, kv, hd)


def paged_slot_positions(pos: jax.Array, ring_cap: jax.Array,
                         length: int) -> jax.Array:
    """Absolute position stored in each logical slot, -1 if never written.

    ``pos`` (B,): tokens inserted so far (including the current one);
    ``ring_cap`` (B,): per-request ring capacity; ``length``: gathered slot
    count (>= ring_cap; slots past ring_cap are unallocated padding).
    Slot s holds the largest p <= pos-1 with p % ring_cap == s.
    """
    idx = jnp.arange(length, dtype=jnp.int32)[None, :]
    last = (pos - 1)[:, None]
    c = ring_cap[:, None]
    stored = last - ((last - idx) % c)
    return jnp.where((idx < c) & (stored >= 0), stored, -1)


def paged_write_indices(pos: jax.Array, ring_cap: jax.Array,
                        block_table: jax.Array, block_size: int,
                        active: jax.Array | None = None):
    """(physical block, in-block offset) for writing position ``pos``.

    pos/ring_cap (B,) or scalar with block_table (B, MB) or (MB,).  Inactive
    slots are redirected to the null block 0 so one scatter serves the whole
    batch without conditionals (active requests always own disjoint blocks,
    so the scatter never has conflicting updates on real blocks).
    """
    li = (pos % ring_cap).astype(jnp.int32)
    off = li % block_size
    if block_table.ndim == 1:                       # single request row
        pb = block_table[li // block_size]
    else:
        b = block_table.shape[0]
        pb = block_table[jnp.arange(b, dtype=jnp.int32), li // block_size]
    if active is not None:
        pb = jnp.where(active, pb, 0)
        off = jnp.where(active, off, 0)
    return pb, off


def paged_multi_write_indices(positions: jax.Array, ring_cap: jax.Array,
                              block_tables: jax.Array, block_size: int,
                              write_mask: jax.Array | None = None):
    """(physical block, in-block offset) for writing a span of positions.

    The multi-token sibling of ``paged_write_indices``, used by the
    speculative verify / draft catch-up steps: ``positions`` (B, W) are each
    slot's absolute positions, ``ring_cap`` (B,) the per-slot ring
    capacities, ``block_tables`` (B, MB) the per-slot tables.  Positions
    whose ``write_mask`` (B, W) entry is False — inactive slots, or a
    catch-up position whose KV is already valid (rewriting it could perturb
    a shared prefix-cache block) — are redirected to the null block 0, so
    one fixed-shape scatter serves every slot regardless of churn.
    """
    li = (positions % ring_cap[:, None]).astype(jnp.int32)
    off = li % block_size
    pb = jnp.take_along_axis(block_tables, li // block_size, axis=1)
    if write_mask is not None:
        pb = jnp.where(write_mask, pb, 0)
        off = jnp.where(write_mask, off, 0)
    return pb, off


def paged_write(arena: jax.Array, pb: jax.Array, off: jax.Array,
                x: jax.Array) -> jax.Array:
    """Scatter per-token K or V rows ``x`` (*idx, KV, hd) into the arena
    (N, KV, bs, hd) at physical blocks ``pb`` / in-block offsets ``off``
    (both shaped ``idx``, from ``paged_write_indices`` or
    ``paged_multi_write_indices``)."""
    return arena.at[pb, :, off].set(x.astype(arena.dtype))


def paged_decode_attention(q: jax.Array, k_arena: jax.Array,
                           v_arena: jax.Array, block_table: jax.Array,
                           pos: jax.Array, ring_cap: jax.Array, *,
                           window: Optional[int] = None) -> jax.Array:
    """One-token attention over the paged arena.

    q (B,1,H,hd); arenas (N, KV, bs, hd); block_table (B, MB); pos (B,) =
    tokens inserted including the current one (whose K/V must already be in
    the arena); ring_cap (B,) per-request ring capacity.  Equivalent to
    ``decode_attention`` on a dense per-request cache (window masking is
    exact even when ring_cap is rounded up to a block multiple, because
    validity is computed from each slot's stored absolute position rather
    than from raw slot age).  Dispatches through
    ``kernels.paged_attention.ops`` — the Pallas flash-decode kernel reads
    arena blocks in place via the block table (DESIGN.md §10); the dense
    gather reference is the off-TPU default.
    """
    from repro.kernels.paged_attention import ops as pops  # late: no cycle
    return pops.paged_attention(q, k_arena, v_arena, block_table, pos,
                                ring_cap, window=window)


def paged_prefill_attention(q: jax.Array, k_hist: jax.Array, v_hist: jax.Array,
                            hist_pos: jax.Array, k_new: jax.Array,
                            v_new: jax.Array, q_pos: jax.Array, *,
                            window: Optional[int] = None) -> jax.Array:
    """Chunked-prefill attention: chunk queries over gathered history + chunk.

    q (B,C,H,hd); k_hist/v_hist (B,L,KV,hd) gathered from the arena with
    stored positions ``hist_pos`` (B,L) (-1 = invalid); k_new/v_new (B,C,KV,hd)
    are this chunk's keys at absolute positions ``q_pos`` (B,C).  Causal and
    sliding-window masks are evaluated on true absolute positions, so the
    result matches a full flash prefill restricted to these queries.
    """
    b, c, h, hd = q.shape
    kv = k_hist.shape[2]
    g = h // kv
    scale = hd ** -0.5
    k_all = jnp.concatenate([k_hist, k_new.astype(k_hist.dtype)], axis=1)
    v_all = jnp.concatenate([v_hist, v_new.astype(v_hist.dtype)], axis=1)
    kpos = jnp.concatenate([hist_pos, q_pos], axis=1)          # (B, L+C)
    qf = (q.astype(jnp.float32) * scale).astype(k_all.dtype)
    qf = qf.reshape(b, c, kv, g, hd)
    s = jnp.einsum("bckgd,bskd->bkgcs", qf, k_all,
                   preferred_element_type=jnp.float32)         # (b,kv,g,C,L+C)
    mask = (kpos[:, None, :] >= 0) & (kpos[:, None, :] <= q_pos[:, :, None])
    if window is not None:
        mask &= (q_pos[:, :, None] - kpos[:, None, :]) < window
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgcs,bskd->bckgd", p.astype(v_all.dtype), v_all,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, c, h, hd).astype(q.dtype)


def decode_attention(q: jax.Array, cache: KVCache, pos: jax.Array) -> jax.Array:
    """One-token attention over the cache. q (B,1,H,hd) -> (B,1,H,hd).

    ``pos``: current absolute position (number of tokens already inserted,
    including this one).  With a ring buffer every slot written so far is a
    valid window member (softmax is permutation-invariant), so validity is
    just slot_index < pos for the full-cache case and "written" for rings.
    """
    b, _, h, hd = q.shape
    cap, kv = cache.k.shape[1], cache.k.shape[2]
    g = h // kv
    scale = hd ** -0.5
    # keep the cache in its storage dtype — casting it to f32 would round-trip
    # the full cache through HBM every layer (§Perf iteration); accumulate the
    # contractions in f32 instead.
    qf = (q.astype(jnp.float32) * scale).astype(cache.k.dtype)
    qf = qf.reshape(b, kv, g, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qf, cache.k,
                   preferred_element_type=jnp.float32)       # (b,kv,g,cap)
    valid = jnp.arange(cap, dtype=jnp.int32)[None, :] < jnp.minimum(pos, cap)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(cache.v.dtype), cache.v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, hd).astype(q.dtype)
