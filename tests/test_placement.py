"""Tier-placement planner (the paper's allocation strategy)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import placement
from repro.data.synthetic import zipf_trace


def _counts(n=1000, seed=0):
    return placement.profile_counts(zipf_trace(n, 20_000, seed=seed), n)


def test_plan_covers_request_share():
    counts = _counts()
    plan = placement.plan_tiers(counts, request_share=0.8)
    assert plan.expected_hot_hit >= 0.8 - 1e-9
    # long-tail: covering 80% of requests needs well under 80% of rows
    assert plan.hot_fraction < 0.5


@given(share=st.floats(0.05, 0.99))
@settings(max_examples=20, deadline=None)
def test_plan_monotone_in_share(share):
    counts = _counts()
    lo = placement.plan_tiers(counts, request_share=share)
    hi = placement.plan_tiers(counts, request_share=min(0.99, share + 0.05))
    assert hi.num_hot >= lo.num_hot
    assert hi.expected_hot_hit >= lo.expected_hot_hit - 1e-12


def test_hot_fraction_and_cap():
    counts = _counts()
    plan = placement.plan_tiers(counts, hot_fraction=0.1)
    assert plan.num_hot == 100
    capped = placement.plan_tiers(counts, request_share=0.99, max_hot_rows=7)
    assert capped.num_hot == 7


def test_split_table_no_double_count():
    counts = _counts(100)
    plan = placement.plan_tiers(counts, request_share=0.5)
    table = jnp.arange(100 * 4, dtype=jnp.float32).reshape(100, 4) + 1.0
    hot, cold = placement.split_table(table, plan)
    assert hot.shape[0] == plan.num_hot
    # hot rows zeroed in cold; every row recoverable from exactly one tier
    recon = np.asarray(cold).copy()
    recon[plan.hot_rows] += np.asarray(hot)
    np.testing.assert_allclose(recon, np.asarray(table))
    assert np.all(np.asarray(cold)[plan.hot_rows] == 0)


def test_bandwidth_balanced_fraction_bounds():
    f = placement.bandwidth_balanced_fraction(counts=_counts())
    assert 0.0 <= f < 1.0
    # faster ICI -> smaller hot tier needed
    f_fast = placement.bandwidth_balanced_fraction(
        counts=_counts(), ici_gbps_per_link=200.0
    )
    assert f_fast <= f


def test_profile_counts_empty_trace():
    counts = placement.profile_counts(np.empty((0,), dtype=np.int64), 16)
    assert counts.shape == (16,) and counts.sum() == 0
    # planning over an all-zero profile is legal and replicates nothing useful
    plan = placement.plan_tiers(counts, request_share=0.8)
    assert plan.expected_hot_hit == 0.0
    assert plan.num_hot <= 16


def test_profile_counts_multi_dim_trace():
    trace = np.array([[1, 1], [3, 1]])
    counts = placement.profile_counts(trace, 5)
    assert counts.tolist() == [0, 3, 0, 1, 0]


def test_plan_tiers_uniform_counts():
    """No skew -> request share needs a proportional row share, and every
    hot-fraction choice hits exactly its fraction of requests."""
    counts = np.full(100, 7, dtype=np.int64)
    plan = placement.plan_tiers(counts, request_share=0.5)
    assert plan.num_hot == 50
    plan = placement.plan_tiers(counts, hot_fraction=0.2)
    assert plan.expected_hot_hit == pytest.approx(0.2)


def test_plan_tiers_single_row_table():
    counts = np.array([42], dtype=np.int64)
    plan = placement.plan_tiers(counts, request_share=0.8)
    assert plan.num_hot == 1
    assert plan.expected_hot_hit == 1.0
    assert plan.hot_slot.tolist() == [0]
    # hot_fraction rounding can't exceed the table
    plan = placement.plan_tiers(counts, hot_fraction=1.0)
    assert plan.num_hot == 1


def test_bandwidth_balanced_fraction_clamping():
    counts = _counts()
    # ICI faster than HBM -> no hot tier needed -> clamps at 0.0
    f = placement.bandwidth_balanced_fraction(
        counts=counts, hbm_gbps=100.0, ici_gbps_per_link=100.0, ici_links=4
    )
    assert f == 0.0
    # ICI vanishing -> everything must be local, clamped below 1.0
    f = placement.bandwidth_balanced_fraction(
        counts=counts, ici_gbps_per_link=1e-6
    )
    assert f == pytest.approx(0.999)
    # safety scales the cold share monotonically
    f_tight = placement.bandwidth_balanced_fraction(counts=counts, safety=0.5)
    f_loose = placement.bandwidth_balanced_fraction(counts=counts, safety=1.0)
    assert f_tight >= f_loose


def test_hot_vector_reduction_curve():
    """The paper's Fig. 12(a): quotient folding shrinks the hot set, but
    sub-linearly (hot rows are scattered, not clustered)."""
    logical = placement.profile_counts(zipf_trace(8192, 40_000, seed=1), 8192)
    curve = placement.hot_vector_reduction_curve(logical, [1, 4, 16, 64])
    assert curve[4] <= curve[1]
    assert curve[16] <= curve[4]
    assert curve[64] <= curve[16]
    # sub-linear: folding by 64 does NOT shrink hot rows by 64x
    assert curve[64] > curve[1] / 64
