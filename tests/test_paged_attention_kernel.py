"""Paged-attention kernel parity (DESIGN.md §10).

Property-based harness: the Pallas flash-decode kernel (interpret mode on
CPU, so the *kernel program* itself is what runs) must match the dense
gather reference on randomized pool states — batch size, GQA ratio, block
size, table width, partial final blocks, sliding windows, multi-token
query spans (speculative catch-up/verify), and post-wraparound ring states
— plus engine-level pins: greedy outputs token-identical between the
kernel and gather paths under the mixed-length churn workload on a dense
GQA arch and the sliding-window MoE arch.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
from helpers import mixed_requests, noisy, small_pool, tiny

from repro.kernels.paged_attention import ops as pops
from repro.kernels.paged_attention.paged import (paged_attention_pallas,
                                                pages_per_block)
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.models import attention as attnmod
from repro.models import transformer as tf
from repro.serve import PagedServer

pytestmark = pytest.mark.tier2  # interpret-mode kernel + engine runs


def _pool_state(rng, b, w, kv, g, hd, bs, mb, window, wrapped, dtype):
    """A random but *reachable* pool state: per-request ring capacities are
    whole blocks, block-table rows hold disjoint physical blocks, and pos
    covers pre-fill, partial-final-block, and post-wraparound regimes."""
    h = kv * g
    ring_blocks = rng.integers(1, mb + 1, size=b)
    n_phys = 1 + int(ring_blocks.sum())
    q = rng.normal(size=(b, w, h, hd)).astype(np.float32)
    k_arena = rng.normal(size=(n_phys, kv, bs, hd)).astype(dtype)
    v_arena = rng.normal(size=(n_phys, kv, bs, hd)).astype(dtype)
    bt = np.zeros((b, mb), np.int32)
    nxt = 1
    for i in range(b):
        for j in range(int(ring_blocks[i])):
            bt[i, j] = nxt
            nxt += 1
    ring = (ring_blocks * bs).astype(np.int32)
    pos = np.zeros(b, np.int32)
    for i in range(b):
        cap = int(ring[i])
        hi = 3 * cap if wrapped else cap
        lo = cap + 1 if (wrapped and hi > cap) else w
        pos[i] = rng.integers(max(lo, w), max(hi, w) + 1)
    return (jnp.asarray(q), jnp.asarray(k_arena), jnp.asarray(v_arena),
            jnp.asarray(bt), jnp.asarray(pos), jnp.asarray(ring))


@settings(max_examples=25, deadline=None)
@given(b=st.integers(1, 3), w=st.sampled_from([1, 2, 4]),
       kv=st.sampled_from([1, 2]), g=st.sampled_from([1, 2, 4]),
       bs=st.sampled_from([4, 8]), mb=st.integers(1, 5),
       window=st.sampled_from([None, 3, 7]),
       wrapped=st.booleans(), seed=st.integers(0, 2**16))
def test_kernel_matches_gather_reference(b, w, kv, g, bs, mb, window,
                                         wrapped, seed):
    rng = np.random.default_rng(seed)
    hd = 8
    q, ka, va, bt, pos, ring = _pool_state(rng, b, w, kv, g, hd, bs, mb,
                                           window, wrapped, np.float32)
    out_k = paged_attention_pallas(q, ka, va, bt, pos, ring, window=window,
                                   interpret=True)
    out_r = paged_attention_ref(q, ka, va, bt, pos, ring, window=window)
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_r), atol=2e-5, rtol=2e-5,
        err_msg=f"b={b} w={w} kv={kv} g={g} bs={bs} mb={mb} "
                f"window={window} wrapped={wrapped} pos={np.asarray(pos)} "
                f"ring={np.asarray(ring)}")


def test_kernel_matches_reference_bf16_arena():
    """bf16 arenas (PoolConfig.kv_dtype) go through the same kernel; the
    comparison is vs the bf16 gather reference at bf16 tolerances."""
    rng = np.random.default_rng(11)
    q, ka, va, bt, pos, ring = _pool_state(rng, 2, 1, 2, 2, 16, 4, 4, None,
                                           True, jnp.bfloat16)
    out_k = paged_attention_pallas(q, ka, va, bt, pos, ring, interpret=True)
    out_r = paged_attention_ref(q, ka, va, bt, pos, ring)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-2, rtol=2e-2)


# ------------------------------------------- multi-page compute blocks
# The kernel walks a slot's live pages in compute blocks of P pages, P
# derived from the shapes (``pages_per_block``).  These cases use the batch
# cell's page (8 KV heads of 128, 16-token blocks), where P < MB, and set
# MB = P + 3 so the last compute block of a full table is partial.

KV8, G2, HD128, BS16 = 8, 2, 128, 16


def _slot_rows(rng, slots, w, mb, dtype):
    """A pool state from explicit slots: ``("live", n)`` holds a full table
    of ``mb`` distinct blocks of which the first ``n`` hold keys;
    ``("ring", r)`` has a ring of ``r`` blocks written 1-3 times round;
    ``None`` is an inactive slot as the engine passes it (pos 1, ring 1,
    every entry on the null block 0), where only the last of ``w`` queries
    has a key."""
    b, h = len(slots), KV8 * G2
    bt = np.zeros((b, mb), np.int32)
    pos = np.ones(b, np.int32)
    ring = np.ones(b, np.int32)
    nxt = 1
    for i, slot in enumerate(slots):
        if slot is None:
            continue
        kind, n = slot
        width = mb if kind == "live" else n
        bt[i, :width] = np.arange(nxt, nxt + width)
        nxt += width
        ring[i] = width * BS16
        if kind == "live":      # the n-th block is the last one holding keys
            pos[i] = rng.integers(max((n - 1) * BS16 + 1, w), n * BS16 + 1)
        else:
            pos[i] = rng.integers(n * BS16 + 1, 3 * n * BS16 + 1)
    q = rng.normal(size=(b, w, h, HD128)).astype(np.float32)
    k_arena = rng.normal(size=(nxt, KV8, BS16, HD128)).astype(np.float32)
    v_arena = rng.normal(size=(nxt, KV8, BS16, HD128)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(k_arena, dtype),
            jnp.asarray(v_arena, dtype), jnp.asarray(bt), jnp.asarray(pos),
            jnp.asarray(ring))


def _assert_kernel_matches(state, dtype, window=None):
    """Kernel vs gather reference on every query that has a key (a query
    with none reads 0 from the kernel and the mean of V from the
    reference; the engine never reads it)."""
    out_k = np.asarray(paged_attention_pallas(*state, window=window,
                                              interpret=True))
    out_r = np.asarray(paged_attention_ref(*state, window=window))
    has_key = np.ones(out_k.shape[:2], bool)
    has_key[np.asarray(state[4]) == 1, :-1] = False
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out_k[has_key], out_r[has_key],
                               atol=tol, rtol=tol,
                               err_msg=f"pos={np.asarray(state[4])}")


@pytest.mark.parametrize("live", ["1", "P-1", "P", "P+1", "MB"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_live_pages_across_compute_blocks(live, dtype):
    """A slot whose live pages end before, at and after a compute block's
    edge, and on a full table whose last compute block is partial, beside
    an inactive slot and a slot with a full table."""
    p = pages_per_block(64, KV8, BS16, HD128, dtype)
    mb = p + 3
    assert 2 <= p and mb % p != 0
    n = {"1": 1, "P-1": p - 1, "P": p, "P+1": p + 1, "MB": mb}[live]
    rng = np.random.default_rng(n)
    state = _slot_rows(rng, [("live", n), None, ("live", mb)], 1, mb, dtype)
    _assert_kernel_matches(state, dtype)


@pytest.mark.parametrize("w", [1, 2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_ring_and_window_over_compute_blocks(w, dtype):
    """Wrapped rings longer than a compute block and a sliding window that
    spans one, for decode (W=1), catch-up (W=2) and verify (W=4) spans."""
    p = pages_per_block(64, KV8, BS16, HD128, dtype)
    mb = p + 3
    rng = np.random.default_rng(100 + w)
    state = _slot_rows(rng, [("ring", p + 2), None, ("ring", 2),
                             ("live", p + 1)], w, mb, dtype)
    _assert_kernel_matches(state, dtype, window=(p + 1) * BS16 - 5)


def test_reference_matches_pre_kernel_decode_math():
    """At W=1 the generalized reference is the original dense-gather decode
    attention — the oracle the engine parity suites were pinned against."""
    rng = np.random.default_rng(3)
    q, ka, va, bt, pos, ring = _pool_state(rng, 3, 1, 2, 2, 8, 4, 4, 5,
                                           True, np.float32)
    got = paged_attention_ref(q, ka, va, bt, pos, ring, window=5)
    # the original inline math, kept verbatim in spirit: gather + softmax
    # over stored>=0 / window validity (no causal term needed at W=1)
    k = attnmod.paged_gather_kv(ka, bt)
    v = attnmod.paged_gather_kv(va, bt)
    b, h, hd = q.shape[0], q.shape[2], q.shape[3]
    length, kv = k.shape[1], k.shape[2]
    g = h // kv
    qf = (q.astype(jnp.float32) * hd ** -0.5).astype(k.dtype)
    s = jnp.einsum("bkgd,bskd->bkgs", qf.reshape(b, kv, g, hd), k,
                   preferred_element_type=jnp.float32)
    stored = attnmod.paged_slot_positions(pos, ring, length)
    valid = (stored >= 0) & (stored > (pos[:, None] - 1) - 5)
    s = jnp.where(valid[:, None, None, :], s, attnmod.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    want = out.reshape(b, 1, h, hd).astype(q.dtype)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_forced_path_dispatch():
    """set_forced_path and the paged_kernel scope drive the dispatcher the
    way the engine and the parity CI leg rely on."""
    assert not pops.kernel_enabled()            # CPU default: gather
    with pops.paged_kernel(True):
        assert pops.kernel_enabled()
        with pops.paged_kernel(False):
            assert not pops.kernel_enabled()
        assert pops.kernel_enabled()
    assert not pops.kernel_enabled()
    pops.set_forced_path("pallas")
    try:
        with pops.paged_kernel(False):
            assert pops.kernel_enabled()        # forced path wins
    finally:
        pops.set_forced_path(None)


# --------------------------------------------------- engine-level parity


@pytest.mark.parametrize("arch", ["llama2-7b", "mixtral-8x7b"])
def test_engine_kernel_vs_gather_greedy_parity(arch):
    """Greedy outputs are token-identical between --paged-kernel and the
    gather path under the mixed-length churn workload (dense GQA and the
    sliding-window ring — the acceptance pin for DESIGN.md §10)."""
    cfg = tiny(arch)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    reqs = mixed_requests(cfg)
    ref = PagedServer(cfg, params, small_pool(), paged_kernel=False).run(
        [dataclasses.replace(r) for r in reqs])
    eng = PagedServer(cfg, params, small_pool(), paged_kernel=True)
    got = eng.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(
            got[r.rid].tokens, ref[r.rid].tokens,
            err_msg=f"{arch}: rid={r.rid}")
    assert eng.decode_trace_count == 1          # kernel path still no-retrace


def test_engine_speculative_kernel_parity():
    """The kernel path's write-then-read verify/catch-up ordering stays
    token-identical on the windowed arch (lookahead reservation keeps the
    up-to-k-past-frontier writes off live history)."""
    cfg = tiny("mixtral-8x7b")
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    reqs = mixed_requests(cfg, n=3)
    ref = PagedServer(cfg, params, small_pool(), paged_kernel=False).run(
        [dataclasses.replace(r) for r in reqs])
    spec = PagedServer(cfg, params, small_pool(), paged_kernel=True,
                       draft_params=noisy(params, 0.005), speculate=3)
    got = spec.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(got[r.rid].tokens, ref[r.rid].tokens,
                                      err_msg=f"rid={r.rid}")
    assert spec.verify_trace_count == 1
