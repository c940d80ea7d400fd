"""Dispatching wrapper for fused flash attention."""
from __future__ import annotations

import jax

from repro.kernels import default_interpret

from .flash import flash_attention_pallas
from .ref import attention_ref

_FORCE_PATH: str | None = None


def set_forced_path(path: str | None) -> None:
    global _FORCE_PATH
    assert path in (None, "pallas", "ref")
    _FORCE_PATH = path


def attention(q, k, v, *, causal: bool = True, window: int | None = None):
    path = _FORCE_PATH
    if path is None:
        path = "pallas" if jax.default_backend() == "tpu" else "ref"
    if path == "pallas":
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      interpret=default_interpret())
    return attention_ref(q, k, v, causal=causal, window=window)
