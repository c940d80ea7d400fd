"""Dispatching wrapper for the RaBitQ code-search kernel."""
from __future__ import annotations

import jax

from repro.core.rabitq import RabitqCodes
from repro.kernels import default_interpret
from .quantize import quantize_pallas
from .ref import quantize_ref

_FORCE_PATH: str | None = None


def set_forced_path(path: str | None) -> None:
    global _FORCE_PATH
    assert path in (None, "pallas", "ref")
    _FORCE_PATH = path


def quantize(w: jax.Array, bits: int, n_candidates: int = 12) -> RabitqCodes:
    path = _FORCE_PATH
    if path is None:
        path = "pallas" if jax.default_backend() == "tpu" else "ref"
    if path == "pallas":
        codes, rescale = quantize_pallas(
            w, bits=bits, n_candidates=n_candidates,
            interpret=default_interpret())
    else:
        codes, rescale = quantize_ref(w, bits, n_candidates)
    return RabitqCodes(codes=codes, rescale=rescale, bits=bits)
