"""Extended RaBitQ properties: the paper's eq. 11 error bound, approximate
unbiasedness, and monotone improvement in bits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import hadamard as h
from repro.core import rabitq


def _rotated_weights(key, d, c):
    w = jax.random.normal(key, (d, c))
    s = h.rademacher(jax.random.fold_in(key, 1), d)
    return h.rht(w, s, axis=0)


@settings(deadline=None, max_examples=12)
@given(bits=st.sampled_from([1, 2, 3, 4, 6, 8]),
       d=st.sampled_from([256, 1024]),
       seed=st.integers(0, 2**31 - 1))
def test_error_bound_eq11(bits, d, seed):
    """|<x,w> - est| < C/(sqrt(d) 2^b) ||x|| ||w|| for ~99.9% of entries."""
    key = jax.random.PRNGKey(seed)
    w = _rotated_weights(key, d, 48)
    q = rabitq.quantize(w, bits)
    x = jax.random.normal(jax.random.fold_in(key, 2), (32, d))
    est = rabitq.estimate_matmul(x, q)
    ref = x @ w
    scale = (jnp.linalg.norm(x, axis=1)[:, None]
             * jnp.linalg.norm(w, axis=0)[None, :])
    rel = np.asarray(jnp.abs(est - ref) / scale)
    bound = rabitq.C_ERROR / (np.sqrt(d) * 2 ** bits)
    assert (rel < bound).mean() > 0.985, (rel.max(), bound)


def test_near_unbiased():
    d, c = 1024, 64
    key = jax.random.PRNGKey(0)
    w = _rotated_weights(key, d, c)
    q = rabitq.quantize(w, 2)
    x = jax.random.normal(jax.random.fold_in(key, 3), (256, d))
    err = np.asarray(rabitq.estimate_matmul(x, q) - x @ w)
    scale = float(np.abs(np.asarray(x @ w)).std())
    assert abs(err.mean()) < 0.02 * scale


def test_more_bits_less_error():
    d, c = 512, 32
    w = _rotated_weights(jax.random.PRNGKey(5), d, c)
    x = jax.random.normal(jax.random.PRNGKey(6), (16, d))
    errs = []
    for bits in (1, 2, 4, 8):
        q = rabitq.quantize(w, bits)
        errs.append(float(jnp.abs(rabitq.estimate_matmul(x, q) - x @ w).mean()))
    assert errs == sorted(errs, reverse=True)
    assert errs[-1] < errs[0] / 20


def test_codes_in_range():
    for bits in (1, 3, 8):
        w = _rotated_weights(jax.random.PRNGKey(7), 128, 8)
        q = rabitq.quantize(w, bits)
        assert int(q.codes.max()) <= (1 << bits) - 1
        assert q.codes.dtype == jnp.uint8


def test_dequantize_matches_estimator():
    w = _rotated_weights(jax.random.PRNGKey(8), 256, 16)
    q = rabitq.quantize(w, 4)
    x = jax.random.normal(jax.random.PRNGKey(9), (8, 256))
    np.testing.assert_allclose(x @ rabitq.dequantize(q),
                               rabitq.estimate_matmul(x, q), rtol=2e-4,
                               atol=2e-4)


def test_invalid_bits():
    with pytest.raises(ValueError):
        rabitq.quantize(jnp.ones((8, 4)), 9)
