"""Pallas TPU kernel: extended-RaBitQ code search + LS rescale, per column.

Grid over output-column tiles; each kernel instance holds a full (d, bc)
column slab in VMEM and runs the S-candidate grid-step sweep entirely
on-chip (reductions over d on the VPU), then emits codes + the closed-form
least-squares rescale.  The sweep is unrolled (S is static and small), so the
compiler can keep w and the running best in registers/VMEM — the CPU-bound
per-vector search of the reference implementation becomes one pass of
vector work per slab.

VMEM budget: ~3 slabs of (d, bc) f32 (w, v, best-v bookkeeping); ops.py picks
bc so that stays under ~8 MB even at d = 20480.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(w_ref, scales_ref, codes_ref, rescale_ref, *, bits: int, n_cand: int):
    w = w_ref[...].astype(jnp.float32)                    # (d, bc)
    levels = float((1 << bits) - 1)
    c_b = levels / 2.0
    absmax = jnp.max(jnp.abs(w), axis=0, keepdims=True)   # (1, bc)
    delta0 = jnp.maximum(absmax, 1e-30) / c_b
    best_err = jnp.full(absmax.shape, jnp.inf, jnp.float32)
    best_delta = delta0
    for s in range(n_cand):
        delta = delta0 * scales_ref[0, s]
        v = jnp.clip(jnp.round(w / delta + c_b), 0.0, levels) - c_b
        wv = jnp.sum(w * v, axis=0, keepdims=True)
        vv = jnp.maximum(jnp.sum(v * v, axis=0, keepdims=True), 1e-30)
        err = -(wv * wv) / vv
        take = err < best_err
        best_err = jnp.where(take, err, best_err)
        best_delta = jnp.where(take, delta, best_delta)
    v = jnp.clip(jnp.round(w / best_delta + c_b), 0.0, levels) - c_b
    wv = jnp.sum(w * v, axis=0, keepdims=True)
    vv = jnp.maximum(jnp.sum(v * v, axis=0, keepdims=True), 1e-30)
    codes_ref[...] = (v + c_b).astype(jnp.uint8)
    rescale_ref[...] = jnp.where(vv > 1e-29, wv / vv, 0.0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("bits", "n_candidates", "bc",
                                             "interpret"))
def quantize_pallas(w: jax.Array, *, bits: int, n_candidates: int = 12,
                    lo: float = 0.3, hi: float = 1.05, bc: int | None = None,
                    interpret: bool = False):
    """Quantize columns of w (d, c): returns (codes uint8 (d, c), rescale (c,))."""
    d, c = w.shape
    if bc is None:
        bc = max(8, min(128, (8 * 1024 * 1024 // 12) // max(d, 1)))
    c_pad = pl.cdiv(c, bc) * bc
    wp = jnp.zeros((d, c_pad), jnp.float32).at[:, :c].set(w.astype(jnp.float32))
    scales = jnp.geomspace(lo, hi, n_candidates, dtype=jnp.float32).reshape(1, -1)
    codes, rescale = pl.pallas_call(
        functools.partial(_kernel, bits=bits, n_cand=n_candidates),
        grid=(c_pad // bc,),
        in_specs=[
            pl.BlockSpec((d, bc), lambda j: (0, j)),
            pl.BlockSpec((1, n_candidates), lambda j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((d, bc), lambda j: (0, j)),
            pl.BlockSpec((1, bc), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, c_pad), jnp.uint8),
            jax.ShapeDtypeStruct((1, c_pad), jnp.float32),
        ],
        interpret=interpret,
    )(wp, scales)
    return codes[:, :c], rescale[0, :c]
