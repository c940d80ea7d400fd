"""Bit-packing of RaBitQ codes.

Codes are stored packed along the d (input/contraction) axis:
  * bits in {1, 2, 4}: dense — ``per = 8 // bits`` codes per uint8,
  * bits in {3, 5, 6, 7, 8}: one code per uint8, counted at b logical bits
    for budget purposes (paper counts logical bits; physical density for
    non-power-of-2 widths is a storage-format detail orthogonal to the method).

Dense codes are packed in tiles of ``PACK_ROWS`` code rows.  Within a tile,
bit plane ``s`` (bits ``[s*bits, (s+1)*bits)`` of every byte) holds the
contiguous row band ``[s*R, (s+1)*R)`` with ``R = PACK_ROWS // per``, so
packed row ``i`` of a tile holds code rows ``i, R + i, 2R + i, ...``.
Unpacking a tile is then ``per`` shift-and-mask passes whose results stack
along rows — no row interleave — which is what the qmatmul Pallas kernel
does in VMEM next to the MXU.  d is zero-padded to a whole tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["pack_codes", "unpack_codes", "packed_rows", "codes_per_byte",
           "DENSE_BITS", "PACK_ROWS"]

DENSE_BITS = (1, 2, 4)
PACK_ROWS = 256


def codes_per_byte(bits: int) -> int:
    return 8 // bits if bits in DENSE_BITS else 1


def packed_rows(d: int, bits: int) -> int:
    per = codes_per_byte(bits)
    if per == 1:
        return d
    return -(-d // PACK_ROWS) * (PACK_ROWS // per)


def pack_codes(codes: jax.Array, bits: int) -> jax.Array:
    """Pack (d, c) uint8 codes -> (packed_rows(d, bits), c) uint8."""
    per = codes_per_byte(bits)
    if per == 1:
        return codes.astype(jnp.uint8)
    d, c = codes.shape
    pad = (-d) % PACK_ROWS
    codes = jnp.pad(codes.astype(jnp.uint8), ((0, pad), (0, 0)))
    planes = codes.reshape(-1, per, PACK_ROWS // per, c)     # (tiles, s, i, c)
    shifts = (jnp.arange(per, dtype=jnp.uint8) * bits)[None, :, None, None]
    return jnp.sum(planes << shifts, axis=1, dtype=jnp.uint8).reshape(-1, c)


def unpack_codes(packed: jax.Array, bits: int, d: int) -> jax.Array:
    """Inverse of ``pack_codes`` -> (d, c) uint8."""
    per = codes_per_byte(bits)
    if per == 1:
        return packed
    c = packed.shape[-1]
    tiles = packed.reshape(-1, 1, PACK_ROWS // per, c)
    shifts = (jnp.arange(per, dtype=jnp.uint8) * bits)[None, :, None, None]
    planes = (tiles >> shifts) & jnp.uint8((1 << bits) - 1)
    return planes.reshape(-1, c)[:d]
