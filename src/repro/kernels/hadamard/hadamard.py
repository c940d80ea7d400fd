"""Pallas TPU kernel: randomized Hadamard transform of VMEM row tiles.

Kronecker factorization H_{d} = H_{d/m} (x) H_{m} with m = min(d, 128): each
128-lane chunk of a (bn, d) row tile is contracted with H_{128} on the MXU,
then an unrolled butterfly over the d/128 chunks applies H_{d/128} with
vector adds.  Both stages use only lane-aligned slices and a lane
concatenation — the TPU's vector layout has no cheap reshape of the lane
axis, so the (bn, d1, d2) reshape-and-swap form does not lower.

The Rademacher sign flip is fused as a pre-multiply on the input tile.
``hadamard_rows`` is shared with the fused RHT+qmatmul kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.hadamard import hadamard_matrix

LANES = 128


def chunk_hadamard(d: int) -> jax.Array:
    """The normalized H_m that ``hadamard_rows`` contracts each chunk with."""
    return hadamard_matrix(min(d, LANES))


def hadamard_rows(x: jax.Array, h: jax.Array) -> jax.Array:
    """Normalized H_d applied to every row of x (bn, d), d a power of 2;
    ``h = chunk_hadamard(d)``."""
    m = h.shape[0]
    n = x.shape[1] // m
    chunks = [jnp.dot(x[:, i * m:(i + 1) * m], h,
                      preferred_element_type=jnp.float32) for i in range(n)]
    step = 1
    while step < n:                                  # H_{d/m} across chunks
        for i in range(0, n, 2 * step):
            for j in range(i, i + step):
                a, b = chunks[j], chunks[j + step]
                chunks[j], chunks[j + step] = a + b, a - b
        step *= 2
    if n == 1:
        return chunks[0]
    return jnp.concatenate(chunks, axis=1) * (1.0 / math.sqrt(n))


def _kernel(x_ref, signs_ref, h_ref, out_ref):
    x = x_ref[...].astype(jnp.float32) * signs_ref[...]     # (bn, d) fused D
    out_ref[...] = hadamard_rows(x, h_ref[...]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def rht_pallas(x: jax.Array, signs: jax.Array, *, bn: int = 8,
               interpret: bool = False) -> jax.Array:
    """Hadamard(D x) for x (n, d) with d a power of 2 (rows independent)."""
    n, d = x.shape
    if d & (d - 1):
        raise ValueError(f"rht_pallas requires power-of-2 d, got {d}")
    h = chunk_hadamard(d)
    n_pad = pl.cdiv(n, bn) * bn
    xp = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    out = pl.pallas_call(
        _kernel,
        grid=(n_pad // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec(h.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), jnp.float32),
        interpret=interpret, name="rht",
    )(xp, signs.reshape(1, d).astype(jnp.float32), h)
    return out[:n]
