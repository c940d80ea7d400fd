"""Property tests for the FWHT / practical RHT (paper App. A.1, C.2)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import hadamard as h

DIMS_POW2 = [2, 8, 64, 256, 1024, 4096]
DIMS_ANY = [3, 5, 48, 100, 768, 2560, 3072, 5120]


@pytest.mark.parametrize("d", DIMS_POW2)
def test_fwht_involution_and_norm(d):
    x = jax.random.normal(jax.random.PRNGKey(d), (4, d))
    y = h.fwht(x)
    np.testing.assert_allclose(h.fwht(y), x, atol=1e-4)
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)


def test_fwht_matches_dense_matrix():
    d = 128
    x = jax.random.normal(jax.random.PRNGKey(0), (3, d))
    hm = h.hadamard_matrix(d)
    np.testing.assert_allclose(h.fwht(x), x @ hm, atol=1e-4)


def test_fwht_rejects_non_pow2():
    with pytest.raises(ValueError):
        h.fwht(jnp.ones((2, 48)))


@settings(deadline=None, max_examples=20)
@given(d=st.sampled_from(DIMS_ANY), seed=st.integers(0, 2**31 - 1))
def test_practical_rht_preserves_inner_products(d, seed):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d_hat = h.largest_pow2_leq(d)
    s1, s2 = h.rademacher(k1, d_hat), h.rademacher(k2, d_hat)
    a = jax.random.normal(k3, (3, d))
    b = jax.random.normal(k4, (d, 5))
    ta = h.practical_rht(a, s1, s2, axis=-1)
    tb = h.practical_rht(b, s1, s2, axis=0)
    ref = a @ b
    np.testing.assert_allclose(ta @ tb, ref,
                               atol=2e-3 * float(jnp.abs(ref).max() + 1))


@settings(deadline=None, max_examples=20)
@given(d=st.sampled_from(DIMS_ANY), seed=st.integers(0, 2**31 - 1))
def test_practical_rht_inverse(d, seed):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    d_hat = h.largest_pow2_leq(d)
    s1, s2 = h.rademacher(k1, d_hat), h.rademacher(k2, d_hat)
    x = jax.random.normal(k3, (2, d))
    y = h.practical_rht(x, s1, s2, axis=-1)
    np.testing.assert_allclose(h.practical_rht_inverse(y, s1, s2, axis=-1),
                               x, atol=1e-4)


def test_rht_flattens_outliers():
    """The whole point of the rotation: a spiky vector becomes dense."""
    d = 1024
    x = jnp.zeros((1, d)).at[0, 3].set(100.0)
    s = h.rademacher(jax.random.PRNGKey(1), d)
    y = h.rht(x, s)
    assert float(jnp.max(jnp.abs(y))) < 5.0   # 100/sqrt(1024) ~ 3.1
