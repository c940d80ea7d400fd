"""Dense gather reference for paged attention.

The pre-kernel serving path, generalized to ``W >= 1`` queries per request:
gather every request's blocks into a dense ``(B, MB*bs, KV, hd)`` copy
(``paged_gather_kv``) and run a masked softmax with validity derived from
each slot's stored absolute position (``paged_slot_positions``).  This is
both the CPU/dryrun serving path and the oracle the property-based parity
harness (tests/test_paged_attention_kernel.py) checks the Pallas kernel
against; at W=1 it reproduces the original ``paged_decode_attention`` math
(the extra causal term ``stored <= qpos`` is vacuous there, since every
stored position precedes the single query).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import (NEG_INF, paged_gather_kv,
                                    paged_slot_positions)


def paged_attention_ref(q: jax.Array, k_arena: jax.Array, v_arena: jax.Array,
                        block_table: jax.Array, pos: jax.Array,
                        ring_cap: jax.Array, *,
                        window: int | None = None) -> jax.Array:
    """Same contract as ``paged_attention_pallas``: q (B, W, H, hd), arenas
    (N, KV, bs, hd), block_table (B, MB), pos (B,) tokens inserted including
    the last query, ring_cap (B,) -> (B, W, H, hd)."""
    b, w, h, hd = q.shape
    k = paged_gather_kv(k_arena, block_table)       # (B, L, KV, hd)
    v = paged_gather_kv(v_arena, block_table)
    length, kv = k.shape[1], k.shape[2]
    g = h // kv
    scale = hd ** -0.5
    qf = (q.astype(jnp.float32) * scale).astype(k.dtype)
    qf = qf.reshape(b, w, kv, g, hd)
    s = jnp.einsum("bwkgd,bskd->bkgws", qf, k,
                   preferred_element_type=jnp.float32)      # (b,kv,g,W,L)
    stored = paged_slot_positions(pos, ring_cap, length)    # (b, L)
    qpos = (pos[:, None] - w) + jnp.arange(w, dtype=jnp.int32)[None]  # (b, W)
    valid = ((stored >= 0)[:, None, :]
             & (stored[:, None, :] <= qpos[:, :, None]))    # (b, W, L)
    if window is not None:
        valid &= (qpos[:, :, None] - stored[:, None, :]) < window
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgws,bskd->bwkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, w, h, hd).astype(q.dtype)
