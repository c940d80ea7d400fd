"""Dispatching wrapper for the RHT kernel (practical-RHT composition included)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import hadamard as hcore
from repro.kernels import default_interpret
from .hadamard import rht_pallas

_FORCE_PATH: str | None = None


def set_forced_path(path: str | None) -> None:
    global _FORCE_PATH
    assert path in (None, "pallas", "ref")
    _FORCE_PATH = path


def _rht_block(x2: jax.Array, signs: jax.Array) -> jax.Array:
    path = _FORCE_PATH
    if path is None:
        path = "pallas" if jax.default_backend() == "tpu" else "ref"
    if path == "pallas":
        return rht_pallas(x2, signs, interpret=default_interpret())
    return hcore.rht(x2, signs, axis=-1)


def practical_rht(x: jax.Array, signs1: jax.Array, signs2: jax.Array | None
                  ) -> jax.Array:
    """Paper Alg. 5 over the last axis of x (..., d), any d, kernel-backed."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    d_hat = hcore.largest_pow2_leq(d)
    y = x2.at[:, :d_hat].set(_rht_block(x2[:, :d_hat], signs1))
    if d_hat != d:
        y = y.at[:, d - d_hat:].set(_rht_block(y[:, d - d_hat:], signs2))
    return y.reshape(*lead, d)
