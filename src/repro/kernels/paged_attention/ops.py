"""Dispatching wrapper for paged attention — the single chokepoint every
serving attention read (decode, speculative catch-up/verify) routes through.

Paths:
  * TPU           -> real pallas_call (compiled flash-decode kernel),
  * forced pallas -> pallas_call(interpret=True) off-TPU (bit-exact kernel
                     semantics for CI parity / the --paged-kernel A/B),
  * otherwise     -> dense gather reference (same math; the pre-kernel
                     serving path).

Selection mirrors ``kernels/qmatmul/ops.fusion``: the scoped
``paged_kernel(enabled)`` context manager pins kernel-vs-gather for
everything traced inside it (a ``contextvars.ContextVar``, so two engines
in one process can hold opposite settings without racing); outside any
scope the backend decides (kernel on TPU, gather elsewhere — interpret-mode
Pallas is pointlessly slow as a CPU default).  ``set_forced_path`` is the
test override that bypasses both.

Like the qmatmul dispatch, head counts come from the operands: under tensor
parallelism (DESIGN.md §11) the call sites sit inside ``shard_map``, so q
carries n_heads/tp query heads and the arena KV/tp KV heads per shard.  The
GQA group ratio (q heads per KV head) is preserved by the all-or-nothing
attention sharding predicate, so kernel and gather paths both work
unchanged on a shard — they just see a narrower head axis.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax

from repro.kernels import default_interpret

from .paged import paged_attention_pallas
from .ref import paged_attention_ref

_FORCE_PATH: str | None = None  # "pallas" | "ref" | None — tests poke this
_USE_KERNEL: contextvars.ContextVar[bool | None] = contextvars.ContextVar(
    "repro_paged_attention_kernel", default=None)


def set_forced_path(path: str | None) -> None:
    global _FORCE_PATH
    assert path in (None, "pallas", "ref")
    _FORCE_PATH = path


@contextlib.contextmanager
def paged_kernel(enabled: bool | None):
    """Scoped kernel-vs-gather toggle for the paged attention read (True =
    Pallas flash-decode kernel, interpret-mode off TPU; False = dense
    gather reference; None = backend default).  Like ``qops.fusion``, the
    setting applies while tracing inside the ``with`` block and
    nests/unwinds correctly — a jitted engine step keeps whichever path it
    was traced under."""
    token = _USE_KERNEL.set(enabled if enabled is None else bool(enabled))
    try:
        yield
    finally:
        _USE_KERNEL.reset(token)


def kernel_enabled() -> bool:
    """Whether the paged attention read resolves to the Pallas kernel under
    the current scope/backend — read at trace time, e.g. by the verify path
    to decide arena-write ordering (DESIGN.md §10)."""
    return _resolve() == "pallas"


def _resolve() -> str:
    if _FORCE_PATH is not None:
        return _FORCE_PATH
    use = _USE_KERNEL.get()
    if use is None:
        use = jax.default_backend() == "tpu"
    return "pallas" if use else "ref"


def paged_attention(q, k_arena, v_arena, block_table, pos, ring_cap, *,
                    window: int | None = None):
    """q (B, W, H, hd) at absolute positions pos-W..pos-1 (K/V already in
    the arena); arenas (N, KV, bs, hd); block_table (B, MB); pos/ring_cap
    (B,) -> (B, W, H, hd)."""
    if _resolve() == "pallas":
        return paged_attention_pallas(
            q, k_arena, v_arena, block_table, pos, ring_cap, window=window,
            interpret=default_interpret())
    return paged_attention_ref(q, k_arena, v_arena, block_table, pos,
                               ring_cap, window=window)
