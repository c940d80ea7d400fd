"""Pallas TPU kernel: fused flash-attention forward (online softmax).

This is the "next lever" identified by §Perf cells A/B: the pure-jnp chunked
attention pays HBM round-trips for every score/exp/select tensor (measured at
~25-40 % of train-step bytes); fusing the whole (bq, bk) tile pipeline —
scores -> mask -> online softmax -> PV accumulate — into one kernel keeps all
S^2-shaped intermediates in VMEM.  The MXU sees two matmuls per tile; the
accumulator (bq, hd) and the running (m, l) stats live in VMEM scratch across
the KV sweep.

Layout: caller flattens heads into the leading grid dim; GQA is handled by an
index map that routes query-head blocks to their shared KV head (no KV
expansion in HBM).  Causal/window masking is positional, computed in-kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, n_k: int, sk: int, causal: bool,
            window: int | None, scale: float):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    iq = pl.program_id(2)
    q = q_ref[0].astype(jnp.float32) * scale              # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                      # (bk, hd)
    v = v_ref[0].astype(jnp.float32)                      # (bk, hd)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < sk                                      # key padding
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]                                   # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                                # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                       # (bq, 1)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _epilogue():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int | None = None,
                           bq: int = 128, bk: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q (B,S,H,hd), k/v (B,S,KV,hd), H = KV*G -> (B,S,H,hd)."""
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    g = h // kv
    scale = hd ** -0.5
    bq = min(bq, max(8, sq))
    bk = min(bk, max(8, sk))
    nq, nk = pl.cdiv(sq, bq), pl.cdiv(sk, bk)
    sq_p, sk_p = nq * bq, nk * bk
    # flatten (B, H) into the leading axis; keys stay at (B, KV)
    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, hd)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * kv, sk, hd)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * kv, sk, hd)
    if sq_p != sq:
        qf = jnp.pad(qf, ((0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        kf = jnp.pad(kf, ((0, 0), (0, sk_p - sk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, sk_p - sk), (0, 0)))

    def q_index(ib, ih, iq, ik):
        return (ib * h + ih, iq, 0)

    def kv_index(ib, ih, iq, ik):
        return (ib * kv + ih // g, ik, 0)                  # GQA head routing

    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, n_k=nk, sk=sk,
                          causal=causal, window=window, scale=scale),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), q_index),
            pl.BlockSpec((1, bk, hd), kv_index),
            pl.BlockSpec((1, bk, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), q_index),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom
            pltpu.VMEM((bq, hd), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out[:, :sq].reshape(b, h, sq, hd)
    return jnp.moveaxis(out, 1, 2)
