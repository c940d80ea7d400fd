"""QuantizedLinear: apply == x @ reconstruct, tricks reduce error, packing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import packing
from repro.core.qlinear import (QuantizedGrouped, quantize_grouped,
                                quantize_linear, reconstruct_weight)


@settings(deadline=None, max_examples=10)
@given(d=st.sampled_from([96, 256, 300, 768]),
       c=st.sampled_from([32, 100]),
       bits=st.sampled_from([2, 4, 8]),
       seed=st.integers(0, 1000))
def test_apply_equals_reconstruct(d, c, bits, seed):
    key = jax.random.PRNGKey(seed)
    w = jax.random.normal(key, (d, c))
    col_norms = np.abs(np.asarray(jax.random.normal(
        jax.random.fold_in(key, 1), (d,))))
    q = quantize_linear(w, bits, jax.random.fold_in(key, 2),
                        x_col_norms=col_norms, outlier_frac=0.01)
    x = jax.random.normal(jax.random.fold_in(key, 3), (7, d))
    y_apply = q.apply(x)
    y_recon = x @ reconstruct_weight(q)
    np.testing.assert_allclose(y_apply, y_recon, rtol=2e-3, atol=2e-3)


def test_quantization_error_reasonable():
    d, c, bits = 512, 64, 4
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (d, c))
    q = quantize_linear(w, bits, jax.random.fold_in(key, 1))
    w_hat = reconstruct_weight(q)
    rel = float(jnp.linalg.norm(w - w_hat) / jnp.linalg.norm(w))
    assert rel < 0.15


def test_outliers_help_with_spiky_inputs():
    """Column-outlier excluding should reduce error when a few input dims
    carry much larger activations."""
    d, c, bits = 256, 32, 2
    key = jax.random.PRNGKey(3)
    w = jax.random.normal(key, (d, c))
    col_norms = np.ones(d)
    col_norms[:3] = 100.0                   # dims 0..2 are hot
    x = jax.random.normal(jax.random.fold_in(key, 1), (64, d))
    x = x.at[:, :3].mul(100.0)
    ref = x @ w
    q_no = quantize_linear(w, bits, jax.random.fold_in(key, 2),
                           outlier_frac=0.0)
    q_out = quantize_linear(w, bits, jax.random.fold_in(key, 2),
                            x_col_norms=col_norms, outlier_frac=0.02)
    e_no = float(jnp.linalg.norm(q_no.apply(x) - ref))
    e_out = float(jnp.linalg.norm(q_out.apply(x) - ref))
    assert e_out < e_no


def test_centralization_helps_shifted_weights():
    d, c, bits = 256, 32, 2
    key = jax.random.PRNGKey(4)
    base = jax.random.normal(key, (d, 1))
    w = base + 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (d, c))
    q_c = quantize_linear(w, bits, jax.random.fold_in(key, 2), centralize=True)
    q_n = quantize_linear(w, bits, jax.random.fold_in(key, 2), centralize=False)
    e_c = float(jnp.linalg.norm(reconstruct_weight(q_c) - w))
    e_n = float(jnp.linalg.norm(reconstruct_weight(q_n) - w))
    assert e_c < e_n


@pytest.mark.parametrize("bits", [1, 2, 3, 4, 5, 8])
def test_packing_roundtrip(bits):
    codes = jax.random.randint(jax.random.PRNGKey(0), (301, 17), 0,
                               1 << bits).astype(jnp.uint8)
    p = packing.pack_codes(codes, bits)
    u = packing.unpack_codes(p, bits, 301)
    assert (u == codes).all()
    if bits in (1, 2, 4):
        # whole PACK_ROWS tiles of 8 // bits codes per byte
        assert p.shape[0] == -(-301 // packing.PACK_ROWS) * (
            packing.PACK_ROWS // (8 // bits))


def test_grouped_apply_matches_per_expert():
    e, d, c = 4, 128, 48
    key = jax.random.PRNGKey(5)
    w = jax.random.normal(key, (e, d, c))
    qg = quantize_grouped(w, 4, jax.random.fold_in(key, 1))
    x = jax.random.normal(jax.random.fold_in(key, 2), (e, 5, d))
    y = qg.apply(x)
    assert y.shape == (e, 5, c)
    rel = float(jnp.linalg.norm(y - jnp.einsum("ecd,edf->ecf", x, w))
                / jnp.linalg.norm(jnp.einsum("ecd,edf->ecf", x, w)))
    assert rel < 0.15


def test_overhead_bits_accounting():
    w = jax.random.normal(jax.random.PRNGKey(6), (256, 64))
    q = quantize_linear(w, 4, jax.random.PRNGKey(7),
                        x_col_norms=np.ones(256), outlier_frac=0.01)
    ov = q.overhead_bits()
    assert ov > 0
    # overhead should be small vs the 4-bit payload
    assert ov < 0.6 * 4 * 256 * 64
