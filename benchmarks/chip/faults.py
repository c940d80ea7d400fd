"""Faults planted in the serving program's timed path, to show that the
output check fails them.  Each fault is a context manager that patches the
program while it is active; the CPU tests (``tests/test_chipbench_faults.py``)
and ``control.py --fault <name>`` on the chip plant the same ones.  Not
part of a benchmark run.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def patched(obj, name: str, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _kv_unwritten(step: str):
    """The paged program ``step`` of ``repro.models.decode`` returns its
    logits with the KV state it was given, unwritten."""
    from repro.models import decode
    real = getattr(decode, step)

    def stale(cfg, params, caches, *a, **k):
        logits, _ = real(cfg, params, caches, *a, **k)
        return logits, caches

    return patched(decode, step, stale)


def decode_step_paged():
    """The decode step leaves its tokens' KV unwritten."""
    return _kv_unwritten("decode_step_paged")


def prefill_chunk_paged():
    """The prefill chunk leaves its tokens' KV unwritten (session histories
    and turns alike)."""
    return _kv_unwritten("prefill_chunk_paged")


def altered_token():
    """Every fourth token a request is served is the sampled one plus 1."""
    from repro.serve.engine import PagedServer
    sample = PagedServer._sample

    def altered(self, logits, rid, step):
        tok = sample(self, logits, rid, step)
        return (tok + 1) % logits.shape[-1] if step % 4 == 2 else tok

    return patched(PagedServer, "_sample", altered)


FAULTS = {f.__name__: f for f in (decode_step_paged, prefill_chunk_paged,
                                  altered_token)}

