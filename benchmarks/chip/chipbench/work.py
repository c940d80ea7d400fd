"""Operations and bytes per kernel call and per decode step, from logical
shapes.  The roofline and MFU readers divide these by device time, so a
kernel's share reads the same work whatever implements it.

Counts are of what the algorithm needs, not of what an implementation
happens to do: the quantized matmul reads each weight's allocated bits once
(``ceil(d * bits / 8)`` bytes a column) and does the dense-equivalent
``2 n d c`` multiply-adds; paged attention reads the K and V of the live
blocks of each active slot and does ``4 H hd`` operations per (query,
key).  The peak used for a time bound is the chip's bf16 rate, its highest
floating-point one, so a share never reads high for want of a bigger peak.
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)

    def seconds(self, peak_flops: float, peak_bw: float) -> float:
        """The least time the chip could take: the larger bound."""
        return max(self.flops / peak_flops, self.bytes / peak_bw)

    def bound(self, peak_flops: float, peak_bw: float) -> str:
        return ("compute" if self.flops / peak_flops >= self.bytes / peak_bw
                else "memory")


@dataclasses.dataclass(frozen=True)
class QMatrix:
    """One quantized linear as the fused RHT+qmatmul kernel sees it."""
    d_keep: int      # input rows after outlier rows are split off
    c: int           # output columns
    bits: int        # allocated bits per weight
    d_hat: int       # Hadamard block (largest power of two <= d_keep)
    two_blocks: bool  # a second, overlapped Hadamard block (Alg. 5)


def rht_qmatmul(n: int, q: QMatrix) -> Work:
    """One call on ``n`` activation rows: f32 activations in and out, packed
    codes, f32 rescale and sign vectors."""
    codes = math.ceil(q.d_keep * q.bits / 8) * q.c
    side = 4 * q.c + 4 * q.d_hat * (2 if q.two_blocks else 1)
    io = 4 * n * (q.d_keep + q.c)
    return Work(2.0 * n * q.d_keep * q.c, codes + side + io)


def paged_attention(contexts, n_heads: int, n_kv: int, head_dim: int,
                    block_size: int, kv_bytes: int) -> Work:
    """One decode call (one layer) over the active slots, ``contexts`` the
    tokens each one attends to (its query included): the live blocks of K
    and V, and f32 queries and outputs."""
    flops = byts = 0.0
    for t in contexts:
        blocks = math.ceil(t / block_size)
        flops += 4.0 * n_heads * head_dim * t
        byts += 2.0 * blocks * block_size * n_kv * head_dim * kv_bytes
        byts += 2.0 * 4 * n_heads * head_dim
    return Work(flops, byts)


def dense_token_flops(m: dict) -> float:
    """Dense-equivalent operations of one token through every linear and
    the ``lm_head`` (attention over the context excluded)."""
    d, h, kv, hd, f = (m["d_model"], m["n_heads"], m["n_kv"], m["head_dim"],
                       m["d_ff"])
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return 2.0 * (m["n_layers"] * per_layer + d * m["vocab"])


def decode_step_flops(m: dict, contexts) -> float:
    """Useful operations of one decode step: each active slot's token
    through the linears and the ``lm_head``, and attention over its live
    context in every layer."""
    att = 4.0 * m["n_heads"] * m["head_dim"] * m["n_layers"]
    return sum(dense_token_flops(m) + att * t for t in contexts)
