"""Shared tiny-model / workload builders for the serving test suites.

One definition of the mixed-length churn workload, the tiny-arch factory,
and the small paged pool, imported by test_paged_engine.py,
test_prefix_cache.py, test_speculative.py and
test_paged_attention_kernel.py (tests/ is on sys.path via pytest rootdir
insertion).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.models import transformer as tf
from repro.serve import PoolConfig, Request

# Mixed prompt/gen lengths; fewer slots than requests so completions must
# free capacity for queued requests to join mid-flight.
PROMPT_LENS = [5, 9, 16, 3, 11]
GEN_LENS = [12, 4, 9, 7, 5]


def nodrop(cfg):
    """Routing must be batch-composition independent for token parity."""
    if cfg.moe is not None:
        return cfg.with_(moe=dataclasses.replace(cfg.moe,
                                                 capacity_factor=64.0))
    return cfg


def tiny(arch):
    return nodrop(registry.get_tiny(arch))


def tiny_model(arch):
    """(cfg, params) for a tiny no-drop variant of ``arch``."""
    cfg = tiny(arch)
    return cfg, tf.init_params(cfg, jax.random.PRNGKey(0))


def small_pool(**kw) -> PoolConfig:
    """The small paged pool every engine test runs against (tight enough
    that block tables churn, chunked prefill interleaves, and rings wrap)."""
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_context", 32)
    kw.setdefault("prefill_chunk", 4)
    return PoolConfig(**kw)


def mixed_requests(cfg, n: int = len(PROMPT_LENS), seed: int = 0):
    """The mixed-length churn workload (PROMPT_LENS x GEN_LENS)."""
    reqs = []
    for i, (pl, gl) in enumerate(list(zip(PROMPT_LENS, GEN_LENS))[:n]):
        prompt = np.asarray(jax.random.randint(
            jax.random.PRNGKey(seed * 100 + i), (pl,), 0, cfg.vocab),
            np.int32)
        reqs.append(Request(rid=i, prompt=prompt, max_new=gl))
    return reqs


def shared_prefix_requests(cfg, n=4, sys_len=12, tail=4, gen=6, seed=3):
    """n requests sharing a system prompt, each with a distinct tail."""
    rng = np.random.default_rng(seed)
    sys_p = rng.integers(0, cfg.vocab, sys_len).astype(np.int32)
    return [Request(rid=i,
                    prompt=np.concatenate(
                        [sys_p,
                         rng.integers(0, cfg.vocab, tail).astype(np.int32)]),
                    max_new=gen)
            for i in range(n)]


def noisy(params, scale, seed=42):
    """An imperfect draft: the same weights plus gaussian noise — enough
    model mismatch to produce genuinely mixed accept/reject rounds."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = [l + scale * jax.random.normal(k, l.shape, l.dtype)
           if jnp.issubdtype(l.dtype, jnp.floating) else l
           for l, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, out)
