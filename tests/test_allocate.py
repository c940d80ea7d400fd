"""AllocateBits: DP optimality vs brute force (Alg. 4), GCD trick, budgets."""
import math

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import allocate


@settings(deadline=None, max_examples=30)
@given(n=st.integers(2, 6), seed=st.integers(0, 2**31 - 1),
       avg=st.sampled_from([2.0, 3.5, 5.0]))
def test_dp_matches_brute_force(n, seed, avg):
    rng = np.random.default_rng(seed)
    alphas = rng.uniform(0.1, 20.0, n)
    m = (rng.integers(1, 9, n) * 64).tolist()
    budget = int(avg * sum(m))
    bits = [1, 2, 3, 4, 6, 8]
    dp = allocate.allocate_bits(alphas, m, budget, bits)
    bf = allocate.brute_force_allocate(alphas, m, budget, bits)
    assert abs(dp.objective - bf.objective) < 1e-9 * max(1, bf.objective)
    assert dp.total_bits <= budget


def test_budget_respected_and_sensitive_layers_win():
    # layer 0 is 100x more sensitive -> must get >= bits of layer 1
    res = allocate.allocate_bits([100.0, 1.0], [256, 256], 6 * 512,
                                 [1, 2, 3, 4, 6, 8])
    assert res.bits[0] >= res.bits[1]
    assert res.total_bits <= 6 * 512


def test_gcd_trick_reduces_problem():
    m = [4096 * 4096] * 8
    res = allocate.allocate_bits([1.0] * 8, m, 4 * sum(m), [2, 4, 8])
    assert res.gcd >= 4096 * 4096          # all m equal => gcd = m
    assert res.n_slots <= 8 * 8


def test_infeasible_budget_raises():
    with pytest.raises(ValueError):
        allocate.allocate_bits([1.0, 1.0], [128, 128], 100, [2, 4])


def test_equal_sensitivity_uniform_allocation():
    res = allocate.allocate_for_avg_bits([5.0] * 4, [512] * 4, 4.0,
                                         [1, 2, 3, 4, 5, 6, 7, 8])
    assert res.bits == [4, 4, 4, 4]


def test_coarsening_safeguard():
    # coprime sizes -> g = 1 -> slots would exceed cap -> coarsened budget
    m = [999983, 999979, 1000003]          # primes
    res = allocate.allocate_bits([1.0, 2.0, 3.0], m, 4 * sum(m), [2, 4, 8])
    assert res.total_bits <= 4 * sum(m)
    assert res.n_slots <= allocate._MAX_SLOTS


@settings(deadline=None, max_examples=40)
@given(n=st.integers(2, 5), seed=st.integers(0, 2**31 - 1),
       avg=st.floats(1.5, 6.0))
def test_coarsened_dp_never_exceeds_budget(n, seed, avg):
    """Adversarial (coprime-ish) layer sizes under a tiny slot cap: the
    round-to-nearest slot costs under-count real bits, so without the
    verify/repair pass allocate_bits returned total_bits > budget (e.g.
    seed 18 overran a 36118-bit budget by 329 bits)."""
    old = allocate._MAX_SLOTS
    allocate._MAX_SLOTS = 50               # force the coarsened path
    try:
        rng = np.random.default_rng(seed)
        m = [int(x) for x in rng.integers(3, 4001, n)]
        alphas = rng.uniform(0.1, 20.0, n)
        budget = int(avg * sum(m))
        bits = [1, 2, 3, 4, 6, 8]
        if budget < bits[0] * sum(m):
            budget = bits[0] * sum(m)
        dp = allocate.allocate_bits(alphas, m, budget, bits)
        bf = allocate.brute_force_allocate(alphas, m, budget, bits)
        assert dp.total_bits <= budget     # the hard feasibility contract
        # the DP can be suboptimal under coarsened costs, never super-optimal
        assert dp.objective >= bf.objective - 1e-9 * max(1, bf.objective)
    finally:
        allocate._MAX_SLOTS = old


def test_avg_bits_on_directly_constructed_result():
    res = allocate.AllocationResult(bits=[4, 4], total_bits=4096, budget=5000,
                                    objective=0.0, gcd=1, n_slots=5000,
                                    total_params=1024)
    assert res.avg_bits == 4.0
    assert allocate.allocate_bits([1.0], [128], 512, [2, 4]).total_params == 128
