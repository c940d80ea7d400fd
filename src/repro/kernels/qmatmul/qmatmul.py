"""Pallas TPU kernel: fused unpack -> dequant -> GEMM with Alg. 3 epilogue.

    Y = (X @ (codes - c_b)) * r
      = (X @ codes) * r - c_b * rowsum(X) * r

Codes arrive packed (``core/packing.py``: 8 // bits codes per uint8, packed
along the contraction axis d in PACK_ROWS-row tiles with one contiguous row
band per bit plane) and are unpacked *inside* the kernel, so HBM->VMEM
traffic for the weights is b/16 of the bf16 baseline — that is the entire
point of weight-only PTQ at decode time and the term the paper's technique
moves (§Roofline).

Blocking: grid (n/bn, c/bc, d/bk), k innermost so the (bn, bc) f32
accumulator and the (bn, 1) rowsum scratch live in VMEM across the k sweep;
the rescale / z-correction epilogue fires on the last k step.  Every
in-kernel step keeps the TPU's (8, 128) vector layout: the packed uint8
tile widens to int32 before the shifts (8-bit shifts do not lower), each
bit plane unpacks to a contiguous row band so the planes simply stack, and
codes reach f32 through int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hadamard import largest_pow2_leq
from repro.core.packing import PACK_ROWS, codes_per_byte
from repro.kernels.hadamard.hadamard import chunk_hadamard, hadamard_rows

DEFAULT_BN = 128
DEFAULT_BC = 128
DEFAULT_BK = 512


def _unpack_tile(packed: jax.Array, bits: int) -> jax.Array:
    """(bk // per, bc) packed uint8 -> (bk, bc) f32 codes."""
    words = packed.astype(jnp.int32)
    per = codes_per_byte(bits)
    if per == 1:
        return words.astype(jnp.float32)
    band = PACK_ROWS // per
    mask = (1 << bits) - 1
    planes = [(words[t:t + band] >> (s * bits)) & mask
              for t in range(0, words.shape[0], band) for s in range(per)]
    return jnp.concatenate(planes, axis=0).astype(jnp.float32)


def _accumulate(x, packed_ref, acc_ref, zacc_ref, *, bits: int,
                compute_dtype):
    x = x.astype(compute_dtype)                                   # (bn, bk)
    codes = _unpack_tile(packed_ref[...], bits).astype(compute_dtype)
    acc_ref[...] += jnp.dot(x, codes, preferred_element_type=jnp.float32)
    zacc_ref[...] += jnp.sum(x.astype(jnp.float32), axis=1, keepdims=True)


def _epilogue(acc_ref, zacc_ref, rescale_ref, out_ref, *, bits: int):
    c_b = ((1 << bits) - 1) / 2.0
    out_ref[...] = ((acc_ref[...] - c_b * zacc_ref[...]) * rescale_ref[...]
                    ).astype(out_ref.dtype)


def _tiling(n: int, d: int, c: int, bits: int, bn: int, bc: int, bk: int):
    """(bn, n_pad, c_pad, d_pad, packed rows) for an (n, d) x (d, c) call.
    The row block shrinks to the batch (a multiple of 8 sublanes) so a
    decode step does not pay for 128 rows."""
    per = codes_per_byte(bits)
    assert bk % 128 == 0 and (per == 1 or bk % PACK_ROWS == 0)
    bn = min(bn, pl.cdiv(n, 8) * 8)
    d_pad = pl.cdiv(d, bk) * bk
    return (bn, pl.cdiv(n, bn) * bn, pl.cdiv(c, bc) * bc, d_pad,
            d_pad // per)


def _pad(a: jax.Array, shape) -> jax.Array:
    """Zero-pad ``a`` up to ``shape`` (no copy when it already fits)."""
    if a.shape == tuple(shape):
        return a
    return jnp.pad(a, [(0, t - s) for s, t in zip(a.shape, shape)])


def _kernel(x_ref, packed_ref, rescale_ref, out_ref, acc_ref, zacc_ref,
            *, bits: int, n_k: int, compute_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        zacc_ref[...] = jnp.zeros_like(zacc_ref)

    _accumulate(x_ref[...], packed_ref, acc_ref, zacc_ref, bits=bits,
                compute_dtype=compute_dtype)

    @pl.when(k == n_k - 1)
    def _fin():
        _epilogue(acc_ref, zacc_ref, rescale_ref, out_ref, bits=bits)


@functools.partial(jax.jit, static_argnames=("bits", "d", "bn", "bc", "bk",
                                             "interpret", "compute_dtype"))
def quantized_matmul_pallas(x: jax.Array, packed: jax.Array, rescale: jax.Array,
                            *, bits: int, d: int,
                            bn: int = DEFAULT_BN, bc: int = DEFAULT_BC,
                            bk: int = DEFAULT_BK, interpret: bool = False,
                            compute_dtype=jnp.float32) -> jax.Array:
    """x (n, d) f32/bf16, packed (packed_rows, c) uint8, rescale (c,) -> (n, c)."""
    n, _ = x.shape
    c = packed.shape[1]
    bn, n_pad, c_pad, d_pad, p_rows = _tiling(n, d, c, bits, bn, bc, bk)
    per = codes_per_byte(bits)
    n_k = d_pad // bk
    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, n_k=n_k,
                          compute_dtype=compute_dtype),
        grid=(n_pad // bn, c_pad // bc, n_k),
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // per, bc), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bc), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, bc), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, c_pad), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bn, bc), jnp.float32),   # f32 accumulator
            pltpu.VMEM((bn, 1), jnp.float32),    # rowsum(X) for the z term
        ],
        interpret=interpret, name="qmatmul",
    )(_pad(x, (n_pad, d_pad)), _pad(packed, (p_rows, c_pad)),
      _pad(rescale.astype(jnp.float32)[None], (1, c_pad)))
    return out[:n, :c]


# ===================================================== fused RHT + qmatmul


def _practical_rht_rows(x, signs1, signs2, h, *, d: int, d_hat: int):
    """Alg. 5 on every row of a (bn, d_pad) tile whose lanes >= d are zero.

    The second (overlapped) block starts at the unaligned lane d - d_hat, so
    it is brought to lane 0 with a lane rotation, transformed, and rotated
    back — an unaligned lane slice does not lower."""
    d_pad = x.shape[1]
    row = hadamard_rows(x[:, :d_hat] * signs1, h)
    if d_pad > d_hat:
        row = jnp.concatenate([row, x[:, d_hat:]], axis=1)
    if d != d_hat:
        lo = d - d_hat
        r = pltpu.roll(row, d_pad - lo, 1)           # lane lo -> lane 0
        r = jnp.concatenate([hadamard_rows(r[:, :d_hat] * signs2, h),
                             r[:, d_hat:]], axis=1)
        row = pltpu.roll(r, lo, 1)
    return row


def _fused_kernel(x_ref, signs1_ref, signs2_ref, h_ref, packed_ref,
                  rescale_ref, out_ref, xrot_ref, acc_ref, zacc_ref,
                  *, bits: int, n_k: int, bk: int, d: int, d_hat: int,
                  compute_dtype):
    j, k = pl.program_id(1), pl.program_id(2)

    # Rotate once per row block (first (j, k) visit); the result stays
    # resident in VMEM scratch, one (bn, bk) slab per k, for the whole
    # (j, k) sweep — rotated activations never touch HBM (Alg. 3 fused
    # with Alg. 5).
    @pl.when((j == 0) & (k == 0))
    def _rotate():
        row = _practical_rht_rows(
            x_ref[...].astype(jnp.float32), signs1_ref[...],
            signs2_ref[...], h_ref[...], d=d, d_hat=d_hat)
        for t in range(n_k):
            xrot_ref[t] = row[:, t * bk:(t + 1) * bk]

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        zacc_ref[...] = jnp.zeros_like(zacc_ref)

    _accumulate(xrot_ref[k], packed_ref, acc_ref, zacc_ref, bits=bits,
                compute_dtype=compute_dtype)

    @pl.when(k == n_k - 1)
    def _fin():
        _epilogue(acc_ref, zacc_ref, rescale_ref, out_ref, bits=bits)


@functools.partial(jax.jit, static_argnames=("bits", "d", "bn", "bc", "bk",
                                             "interpret", "compute_dtype"))
def rht_quantized_matmul_pallas(x: jax.Array, packed: jax.Array,
                                rescale: jax.Array, signs1: jax.Array,
                                signs2: jax.Array | None, *, bits: int, d: int,
                                bn: int = DEFAULT_BN, bc: int = DEFAULT_BC,
                                bk: int = DEFAULT_BK, interpret: bool = False,
                                compute_dtype=jnp.float32) -> jax.Array:
    """Y = practical_rht(x) @ (r * (codes - c_b)) without the HBM round trip.

    x (n, d) f32/bf16, packed (packed_rows, c) uint8, rescale (c,),
    signs1/signs2 (d_hat,) Rademacher (signs2 None iff d is a power of 2).
    """
    n, _ = x.shape
    c = packed.shape[1]
    d_hat = largest_pow2_leq(d)
    if d_hat != d and signs2 is None:
        raise ValueError("signs2 required when d is not a power of 2")
    if signs2 is None:
        signs2 = jnp.zeros((d_hat,), jnp.float32)            # dead input
    bn, n_pad, c_pad, d_pad, p_rows = _tiling(n, d, c, bits, bn, bc, bk)
    per = codes_per_byte(bits)
    h = chunk_hadamard(d_hat)
    n_k = d_pad // bk
    out = pl.pallas_call(
        functools.partial(_fused_kernel, bits=bits, n_k=n_k, bk=bk, d=d,
                          d_hat=d_hat, compute_dtype=compute_dtype),
        grid=(n_pad // bn, c_pad // bc, n_k),
        in_specs=[
            # same block for every (j, k) -> fetched from HBM once per row block
            pl.BlockSpec((bn, d_pad), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, d_hat), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, d_hat), lambda i, j, k: (0, 0)),
            pl.BlockSpec(h.shape, lambda i, j, k: (0, 0)),
            pl.BlockSpec((bk // per, bc), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bc), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, bc), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, c_pad), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((n_k, bn, bk), jnp.float32),  # rotated activations
            pltpu.VMEM((bn, bc), jnp.float32),       # f32 accumulator
            pltpu.VMEM((bn, 1), jnp.float32),        # rowsum for the z term
        ],
        interpret=interpret, name="rht_qmatmul",
    )(_pad(x, (n_pad, d_pad)),
      signs1.reshape(1, d_hat).astype(jnp.float32),
      signs2.reshape(1, d_hat).astype(jnp.float32), h,
      _pad(packed, (p_rows, c_pad)),
      _pad(rescale.astype(jnp.float32)[None], (1, c_pad)))
    return out[:n, :c]
