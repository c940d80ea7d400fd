"""Ring-buffer wraparound coverage: cache_insert at pos >= cap, prefill
filling past the capacity (the _ring_fill tail branch), decode parity with
the windowed full forward across the wrap boundary, and paged-vs-dense ring
attention equivalence."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.models import attention as attnmod
from repro.models import decode as dec
from repro.models import transformer as tf
from repro.models.attention import KVCache, cache_insert, decode_attention


def test_cache_insert_wraps_to_slot_pos_mod_cap():
    """Inserting positions 0..9 into a cap-4 ring leaves exactly the last 4
    positions, each at slot pos % cap."""
    cap, kv, hd = 4, 2, 8
    cache = KVCache.init(1, cap, kv, hd)
    for pos in range(10):
        k = jnp.full((1, 1, kv, hd), float(pos))
        v = jnp.full((1, 1, kv, hd), float(100 + pos))
        cache = cache_insert(cache, k, v, jnp.int32(pos))
    for pos in range(6, 10):                      # the surviving tail
        slot = pos % cap
        assert float(cache.k[0, slot, 0, 0]) == float(pos)
        assert float(cache.v[0, slot, 0, 0]) == float(100 + pos)


def test_windowed_decode_parity_across_wrap():
    """Ring decode must track the windowed full forward before, at, and well
    past the wrap boundary (prompt < window, generation crosses it twice)."""
    cfg = registry.get_tiny("llama2-7b").with_(window=6)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    b, s_pre, s_tot = 1, 3, 18                    # cap = 6; wraps at pos 6, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s_tot), 0, cfg.vocab)
    logits_full, _ = tf.forward(cfg, params, toks, scan=False)
    lg, caches, _ = dec.prefill(cfg, params, toks[:, :s_pre], context=s_tot,
                                scan=True)
    errs = []
    for t in range(s_pre, s_tot):
        sl, caches = dec.decode_step(cfg, params, caches, toks[:, t:t + 1],
                                     jnp.int32(t), scan=True)
        errs.append(float(jnp.abs(sl - logits_full[:, t]).max()))
    assert max(errs) < 2e-4, errs


def test_ring_fill_long_prompt_then_decode():
    """Prompt longer than the window exercises the _ring_fill tail branch
    (only the last cap tokens are kept, at slots t % cap); decode continuing
    from it must match the windowed full forward."""
    cfg = registry.get_tiny("llama2-7b").with_(window=5)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    b, s_pre, s_tot = 1, 9, 14                    # prompt 9 > cap 5
    toks = jax.random.randint(jax.random.PRNGKey(2), (b, s_tot), 0, cfg.vocab)
    logits_full, _ = tf.forward(cfg, params, toks, scan=False)
    lg, caches, _ = dec.prefill(cfg, params, toks[:, :s_pre], context=s_tot,
                                scan=True)
    assert float(jnp.abs(lg[:, -1] - logits_full[:, s_pre - 1]).max()) < 2e-4
    errs = []
    for t in range(s_pre, s_tot):
        sl, caches = dec.decode_step(cfg, params, caches, toks[:, t:t + 1],
                                     jnp.int32(t), scan=True)
        errs.append(float(jnp.abs(sl - logits_full[:, t]).max()))
    assert max(errs) < 2e-4, errs


def test_paged_ring_matches_dense_ring_attention():
    """paged_decode_attention over a block-ring (capacity rounded up to a
    block multiple, exact window masking) == decode_attention over a dense
    ring of capacity == window, across the wrap boundary."""
    key = jax.random.PRNGKey(3)
    b, kv, h, hd, window, bs = 1, 2, 4, 8, 6, 4
    ring_blocks = -(-window // bs)                # 2 blocks -> ring cap 8
    ring_cap = ring_blocks * bs
    n_blocks = 1 + ring_blocks                    # + null block
    k_arena = jnp.zeros((n_blocks, kv, bs, hd))
    v_arena = jnp.zeros((n_blocks, kv, bs, hd))
    bt = jnp.asarray([[1, 2]], jnp.int32)
    dense = KVCache.init(b, window, kv, hd)
    for pos in range(15):                         # wraps both rings
        kk = jax.random.normal(jax.random.fold_in(key, 2 * pos),
                               (b, 1, kv, hd))
        vv = jax.random.normal(jax.random.fold_in(key, 2 * pos + 1),
                               (b, 1, kv, hd))
        q = jax.random.normal(jax.random.fold_in(key, 1000 + pos),
                              (b, 1, h, hd))
        dense = cache_insert(dense, kk, vv, jnp.int32(pos))
        pb, off = attnmod.paged_write_indices(
            jnp.asarray([pos], jnp.int32), jnp.asarray([ring_cap], jnp.int32),
            bt, bs, jnp.asarray([True]))
        k_arena = attnmod.paged_write(k_arena, pb, off, kk[:, 0])
        v_arena = attnmod.paged_write(v_arena, pb, off, vv[:, 0])
        ref = decode_attention(q, dense, jnp.int32(pos + 1))
        got = attnmod.paged_decode_attention(
            q, k_arena, v_arena, bt, jnp.asarray([pos + 1], jnp.int32),
            jnp.asarray([ring_cap], jnp.int32), window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"pos={pos}")


def test_paged_full_context_matches_dense_cache():
    """Without a window, a never-wrapping block table reproduces the dense
    full-context cache attention exactly."""
    key = jax.random.PRNGKey(4)
    b, kv, h, hd, bs, cap = 1, 2, 2, 8, 4, 12
    nb = cap // bs
    k_arena = jnp.zeros((1 + nb, kv, bs, hd))
    v_arena = jnp.zeros((1 + nb, kv, bs, hd))
    bt = jnp.asarray([[1, 2, 3]], jnp.int32)
    dense = KVCache.init(b, cap, kv, hd)
    for pos in range(cap):
        kk = jax.random.normal(jax.random.fold_in(key, 2 * pos), (b, 1, kv, hd))
        vv = jax.random.normal(jax.random.fold_in(key, 2 * pos + 1),
                               (b, 1, kv, hd))
        q = jax.random.normal(jax.random.fold_in(key, 500 + pos), (b, 1, h, hd))
        dense = cache_insert(dense, kk, vv, jnp.int32(pos))
        pb, off = attnmod.paged_write_indices(
            jnp.asarray([pos], jnp.int32), jnp.asarray([cap], jnp.int32),
            bt, bs, jnp.asarray([True]))
        k_arena = attnmod.paged_write(k_arena, pb, off, kk[:, 0])
        v_arena = attnmod.paged_write(v_arena, pb, off, vv[:, 0])
        ref = decode_attention(q, dense, jnp.int32(pos + 1))
        got = attnmod.paged_decode_attention(
            q, k_arena, v_arena, bt, jnp.asarray([pos + 1], jnp.int32),
            jnp.asarray([cap], jnp.int32), window=None)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5, err_msg=f"pos={pos}")
