"""Dispatching wrapper for the fused dequant GEMM — the single chokepoint the
decode hot path (QuantizedLinear / QuantizedGrouped -> serve/decode) routes
through.

Paths:
  * TPU           -> real pallas_call (compiled kernel),
  * forced pallas -> pallas_call(interpret=True) off-TPU (bit-exact kernel
                     semantics for tests),
  * CPU / dryrun  -> pure-jnp reference (same math; interpret-mode would be
                     pointlessly slow inside a 512-way SPMD dry-run compile).

Fusion: by default the practical RHT (Alg. 5) is applied *inside* the qmatmul
kernel (``rht_quantized_matmul``) so rotated activations never round-trip
through HBM between the Hadamard stage and the dequant GEMM.  The scoped
``fusion(enabled)`` context manager selects the legacy two-kernel composition
for A/B benchmarking (benchmarks/serve_bench.py reports both); it is backed by
a ``contextvars.ContextVar`` so a serving engine and a benchmark running in
the same process cannot race each other's toggles the way a mutable module
global could.

Shapes are taken from the operands, never from a config: under tensor
parallelism (DESIGN.md §11) these entry points run *inside* ``shard_map``
blocks, where the packed codes / rescale / w_out carry per-shard column
counts (c/tp of the full layer).  Every column's estimator (Alg. 3) depends
only on that column's codes and side info plus the full rotated activation,
so a shard computes exactly the columns it owns and the dispatch needs no
TP awareness at all.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret

from .qmatmul import quantized_matmul_pallas, rht_quantized_matmul_pallas
from .ref import quantized_matmul_ref, rht_quantized_matmul_ref

_FORCE_PATH: str | None = None  # "pallas" | "ref" | None (auto) — tests poke this
_FUSE_RHT: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "repro_qmatmul_fuse_rht", default=True)


def set_forced_path(path: str | None) -> None:
    global _FORCE_PATH
    assert path in (None, "pallas", "ref")
    _FORCE_PATH = path


@contextlib.contextmanager
def fusion(enabled: bool):
    """Scoped RHT+GEMM fusion toggle (True = fused single-dispatch kernel,
    the default; False = legacy two-kernel composition where rotated
    activations round-trip through HBM, kept for A/B measurement).

    The setting only applies while tracing/executing inside the ``with``
    block, and nests/unwinds correctly — concurrent contexts (engine vs
    benchmark) each see their own value, so two engines in one process can
    hold opposite settings without racing.  This is the only supported
    toggle; the old process-wide ``set_fused`` mutator has been removed.
    """
    token = _FUSE_RHT.set(bool(enabled))
    try:
        yield
    finally:
        _FUSE_RHT.reset(token)


def fused_enabled() -> bool:
    """Current fusion setting (the innermost enclosing ``fusion`` scope, or
    the fused default when none is active)."""
    return _FUSE_RHT.get()


def resolve_path() -> str:
    """"pallas" or "ref": the path a call traced now takes."""
    path = _FORCE_PATH
    if path is None:
        path = "pallas" if jax.default_backend() == "tpu" else "ref"
    return path


def quantized_matmul(x: jax.Array, packed: jax.Array, rescale: jax.Array,
                     *, bits: int, d: int) -> jax.Array:
    """Estimate X @ (r * (codes - c_b)) for X (..., d) -> (..., c)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if resolve_path() == "pallas":
        y = quantized_matmul_pallas(x2, packed, rescale, bits=bits, d=d,
                                    interpret=default_interpret())
    else:
        y = quantized_matmul_ref(x2, packed, rescale, bits=bits, d=d)
    return y.reshape(*lead, y.shape[-1])


def rht_quantized_matmul(x: jax.Array, packed: jax.Array, rescale: jax.Array,
                         signs1: jax.Array, signs2: jax.Array | None,
                         *, bits: int, d: int) -> jax.Array:
    """Estimate practical_rht(X) @ (r * (codes - c_b)) for X (..., d).

    The decode hot path: with fusion on, the RHT's Kronecker matmuls happen in
    VMEM inside the qmatmul kernel; with fusion off, rotated activations are
    materialized between two kernels (the pre-fusion behavior).
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if not _FUSE_RHT.get():
        from repro.kernels.hadamard import ops as hops  # late: avoid cycle
        xr = hops.practical_rht(x2.astype(jnp.float32), signs1, signs2)
        return quantized_matmul(xr, packed, rescale, bits=bits, d=d
                                ).reshape(*lead, -1)
    if resolve_path() == "pallas":
        y = rht_quantized_matmul_pallas(
            x2, packed, rescale, signs1, signs2, bits=bits, d=d,
            interpret=default_interpret())
    else:
        y = rht_quantized_matmul_ref(x2, packed, rescale, signs1, signs2,
                                     bits=bits, d=d)
    return y.reshape(*lead, y.shape[-1])


def grouped_rht_quantized_matmul(x: jax.Array, packed: jax.Array,
                                 rescale: jax.Array, signs1: jax.Array,
                                 signs2: jax.Array | None,
                                 *, bits: int, d: int) -> jax.Array:
    """Per-expert fused estimate: x (E, C, d), packed (E, pr, c),
    rescale (E, c) -> (E, C, c).  Signs are shared across experts (same input
    space), so the whole MoE FFN is one vmap over the fused kernel — packed
    codes stay packed; no dense (E, d, c) dequant buffer exists at any point.
    """
    return jax.vmap(
        lambda xe, pe, re: rht_quantized_matmul(
            xe, pe, re, signs1, signs2, bits=bits, d=d)
    )(x, packed, rescale)
