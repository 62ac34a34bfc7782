"""Tests for the Eq. (2) cost model (paper §V)."""
import numpy as np
import pytest
from hypothesis import given
from hypothesis import strategies as st
import hypothesis.extra.numpy as hnp

import jax
import jax.numpy as jnp

from repro.core.costmodel import (
    cost_breakdown,
    evaluate_schedule,
    hourly_cost_series,
    hourly_cost_series_jnp,
    monthly_cumsum,
    monthly_cumsum_np,
    prefix_sum,
    tier_segment,
    tiered_marginal_cost_np,
)
from repro.core.pricing import CostParams, flat_rate, make_scenario

P = make_scenario("gcp", "aws")


def demand_strategy(max_t=400, max_p=3):
    return hnp.arrays(
        dtype=np.float64,
        shape=st.tuples(st.integers(1, max_t), st.integers(1, max_p)),
        elements=st.floats(0, 1e4),
    )


@given(demand_strategy())
def test_cost_series_nonnegative_and_shapes(d):
    c = hourly_cost_series(P, d)
    T = d.shape[0]
    for arr in (c.vpn_lease, c.vpn_transfer, c.cci_lease, c.cci_transfer):
        assert arr.shape == (T,)
        assert (arr >= 0).all()


@given(demand_strategy(max_t=200))
def test_schedule_cost_interpolates(d):
    """All-VPN and all-CCI schedules bracket any mixed schedule... not in
    general — but evaluate_schedule must equal the sum of chosen sides."""
    c = hourly_cost_series(P, d)
    T = d.shape[0]
    rng = np.random.default_rng(42)
    x = rng.integers(0, 2, size=T)
    total = evaluate_schedule(P, d, x, costs=c)
    manual = float(np.sum(np.where(x == 1, c.cci, c.vpn)))
    assert total == pytest.approx(manual)


def test_monthly_tier_reset():
    """Tier position resets at month boundaries: hour-0-of-month traffic is
    billed at the first tier even after a huge previous month."""
    params = make_scenario("gcp", "aws")
    m = params.hours_per_month
    d = np.zeros(m + 1)
    d[0] = 5e6        # deep into the cheapest tier in month 0
    d[m - 1] = 100.0  # still billed at the last tier (cum 5e6)
    d[m] = 100.0      # new month: billed at the first tier again
    c = hourly_cost_series(params, d)
    rate_last = c.vpn_transfer[m - 1] / 100.0
    rate_reset = c.vpn_transfer[m] / 100.0
    assert rate_last == pytest.approx(params.vpn_tier.rates[-1])
    assert rate_reset == pytest.approx(params.vpn_tier.rates[0])


def test_tiered_vs_flat_vpn():
    """With a flat vpn tier, transfer cost is exactly rate * volume."""
    params = CostParams(4.55, 0.42, 0.02, 0.105, flat_rate(0.09))
    d = np.abs(np.random.default_rng(0).normal(100, 30, size=(500, 2)))
    c = hourly_cost_series(params, d)
    np.testing.assert_allclose(c.vpn_transfer, 0.09 * d.sum(axis=1), rtol=1e-12)


def test_cci_cost_is_flat_rate():
    d = np.abs(np.random.default_rng(1).normal(100, 30, size=(300,)))
    c = hourly_cost_series(P, d)
    np.testing.assert_allclose(c.cci_transfer, P.c_cci * d, rtol=1e-12)
    np.testing.assert_allclose(c.cci_lease, P.L_cci + P.V_cci)


def test_per_pair_tier_accumulation():
    """Tiers accumulate per pair: one pair at 2x rate hits cheap tiers sooner
    than two pairs at 1x rate each (same aggregate)."""
    params = make_scenario("gcp", "aws")
    T = 2000
    one = np.full((T, 1), 2000.0)
    two = np.full((T, 2), 1000.0)
    c1 = hourly_cost_series(params, one).vpn.sum()
    c2 = hourly_cost_series(params, two).vpn.sum()
    assert c1 < c2 - params.L_vpn * T * 0.5  # also pays one less lease


def test_breakdown_sums_to_total():
    d = np.abs(np.random.default_rng(2).normal(50, 20, size=(400, 2)))
    x = np.random.default_rng(3).integers(0, 2, size=400)
    b = cost_breakdown(P, d, x)
    assert b["total"] == pytest.approx(b["lease"] + b["transfer"])
    assert b["total"] == pytest.approx(evaluate_schedule(P, d, x))


@given(demand_strategy(max_t=300, max_p=2))
def test_jnp_matches_numpy(d):
    c = hourly_cost_series(P, d)
    cj = hourly_cost_series_jnp(P, jnp.asarray(d, jnp.float32))
    np.testing.assert_allclose(np.asarray(cj["vpn"]), c.vpn, rtol=2e-3, atol=1e-2)
    np.testing.assert_allclose(np.asarray(cj["cci"]), c.cci, rtol=2e-3, atol=1e-2)


@given(
    start=st.floats(0, 1e6),
    add=hnp.arrays(np.float64, st.integers(1, 50), elements=st.floats(0, 1e4)),
)
def test_vectorized_tier_matches_scalar(start, add):
    tier = P.vpn_tier
    vec = tiered_marginal_cost_np(tier, np.full(add.shape, start), add)
    ref = np.array([tier.marginal_cost(start, a) for a in add])
    np.testing.assert_allclose(vec, ref, rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("hpm", [1, 7, 730])
def test_device_prefix_sums_match_numpy_bitwise(hpm):
    """The device month volumes and prefix sums add in numpy's order."""
    rng = np.random.default_rng(hpm)
    d = rng.lognormal(4.0, 2.0, size=(3, 2000))
    with jax.enable_x64():
        got = np.asarray(monthly_cumsum(jnp.asarray(d), hpm))
        pref = np.asarray(prefix_sum(jnp.asarray(d)))
    np.testing.assert_array_equal(got, monthly_cumsum_np(d, hpm))
    np.testing.assert_array_equal(pref, np.cumsum(d, axis=-1))
    assert (got[:, ::hpm] == 0).all()


@given(
    lo=st.floats(0, 1e7), d=st.floats(0, 1e4),
    prev=st.floats(0, 2e5), width=st.floats(0, 2e5),
)
def test_tier_segment_is_the_clipped_overlap(lo, d, prev, width):
    """``tier_segment`` is ``clip(min(lo + d, b) - max(lo, prev), 0)``, and
    exactly ``d`` when the hour stays inside the tier."""
    bound = prev + width
    seg = float(tier_segment(lo, d, prev, bound, np))
    want = max(0.0, min(lo + d, bound) - max(lo, prev))
    assert seg == pytest.approx(want, rel=1e-12, abs=1e-9)
    if prev <= lo and min(bound - lo, bound - prev) >= d:
        assert seg == d
