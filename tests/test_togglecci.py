"""Tests for the ToggleCCI FSM (paper §VI, Fig. 5)."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
import hypothesis.extra.numpy as hnp

import jax
import jax.numpy as jnp

from repro.core.costmodel import hourly_cost_series
from repro.core.pricing import CostParams, flat_rate, make_scenario
from repro.core.togglecci import OFF, ON, WAITING, run_togglecci, run_togglecci_scan
from repro.traffic.traces import bursty_trace, constant_trace

P = make_scenario("gcp", "aws")


def small_params(**kw):
    kw.setdefault("D", 5)
    kw.setdefault("T_cci", 12)
    kw.setdefault("h", 12)
    return CostParams(1.0, 0.1, 0.02, 0.1, flat_rate(0.1), **kw)


def demand_strategy(max_t=500):
    return hnp.arrays(np.float64, st.integers(10, max_t), elements=st.floats(0, 5e3))


# ---------------------------------------------------------------------------
# FSM invariants
# ---------------------------------------------------------------------------


@given(demand_strategy())
def test_fsm_invariants(d):
    params = small_params()
    res = run_togglecci(params, d)
    st_tr, x = res.state, res.x
    # x == 1 exactly in ON.
    np.testing.assert_array_equal(x == 1, st_tr == ON)
    # WAITING runs are exactly D hours followed by ON.
    t = 0
    T = len(st_tr)
    while t < T:
        if st_tr[t] == WAITING:
            run = 0
            while t < T and st_tr[t] == WAITING:
                run += 1
                t += 1
            if t < T:  # not truncated by horizon
                assert run == params.D
                assert st_tr[t] == ON
        else:
            t += 1
    # ON runs last at least T_cci hours (unless truncated by the horizon).
    t = 0
    while t < T:
        if st_tr[t] == ON:
            run = 0
            while t < T and st_tr[t] == ON:
                run += 1
                t += 1
            if t < T:
                assert run >= params.T_cci
        else:
            t += 1


@given(demand_strategy())
def test_initial_state_off(d):
    res = run_togglecci(small_params(), d)
    assert res.state[0] in (OFF, WAITING)  # hour 0 can request, never serve CCI
    assert res.x[0] == 0


def test_zero_demand_stays_off():
    d = np.zeros(1000)
    res = run_togglecci(small_params(), d)
    assert (res.state == OFF).all()
    assert res.total_cost == pytest.approx(1000 * small_params().L_vpn)


def test_sustained_high_demand_activates():
    params = small_params()
    d = np.full(500, 1e4)  # VPN at 0.1 $/GB vs CCI at 0.02 -> CCI wins big
    res = run_togglecci(params, d)
    assert len(res.requests) == 1
    first_on = np.argmax(res.state == ON)
    assert res.state[first_on - 1] == WAITING
    assert (res.state[first_on:] == ON).all(), "high demand: stays ON forever"


def test_hysteresis_prevents_oscillation():
    """Demand hovering at breakeven: two thresholds (0.9/1.1) must produce
    far fewer mode switches than a single threshold (1.0/1.0)."""
    from repro.core.pricing import breakeven_rate_gb_per_hour

    rate = breakeven_rate_gb_per_hour(P)
    rng = np.random.default_rng(7)
    d = rate * rng.normal(1.0, 0.15, size=5000).clip(0, None)
    hyst = run_togglecci(P, d)
    import dataclasses

    single = dataclasses.replace(P, theta1=1.0, theta2=1.0)
    nohyst = run_togglecci(single, d)
    switches = lambda r: len(r.requests) + len(r.releases)
    assert switches(hyst) <= switches(nohyst)


def test_renew_in_chunks_releases_only_at_multiples():
    params = small_params()
    rng = np.random.default_rng(3)
    d = np.where(rng.random(800) < 0.5, 1e4, 0.0)
    res = run_togglecci(params, d, renew_in_chunks=True)
    # Every ON run must be an exact multiple of T_cci (unless horizon-cut).
    t, T = 0, len(res.state)
    while t < T:
        if res.state[t] == ON:
            run = 0
            while t < T and res.state[t] == ON:
                run += 1
                t += 1
            if t < T:
                assert run % params.T_cci == 0
        else:
            t += 1


# ---------------------------------------------------------------------------
# scan implementation equivalence
# ---------------------------------------------------------------------------


@given(demand_strategy(max_t=400))
@settings(max_examples=15)
def test_scan_matches_python(d):
    params = small_params()
    costs = hourly_cost_series(params, d)
    ref = run_togglecci(params, d, costs=costs)
    out = run_togglecci_scan(
        params, jnp.asarray(costs.vpn, jnp.float32), jnp.asarray(costs.cci, jnp.float32)
    )
    np.testing.assert_array_equal(np.asarray(out["x"]), ref.x)
    np.testing.assert_array_equal(np.asarray(out["state"]), ref.state)
    assert float(out["total_cost"]) == pytest.approx(ref.total_cost, rel=1e-4)


def test_scan_matches_python_paper_params_bursty():
    d = bursty_trace(seed=11).sum(axis=1)
    costs = hourly_cost_series(P, d)
    ref = run_togglecci(P, d, costs=costs)
    out = run_togglecci_scan(P, jnp.asarray(costs.vpn), jnp.asarray(costs.cci))
    np.testing.assert_array_equal(np.asarray(out["x"]), ref.x)


def test_scan_vmaps_over_scenarios():
    ds = np.stack([bursty_trace(seed=s).sum(axis=1) for s in range(4)])
    vpn = np.stack([hourly_cost_series(P, d).vpn for d in ds])
    cci = np.stack([hourly_cost_series(P, d).cci for d in ds])
    fn = jax.vmap(lambda v, c: run_togglecci_scan(P, v, c)["total_cost"])
    totals = np.asarray(fn(jnp.asarray(vpn), jnp.asarray(cci)))
    refs = np.array([run_togglecci(P, d).total_cost for d in ds])
    np.testing.assert_allclose(totals, refs, rtol=1e-4)


# ---------------------------------------------------------------------------
# Window-sum precision (float64 regression) + traceable ToggleParams
# ---------------------------------------------------------------------------


def _straddling_costs(T=4096, h=24):
    """Costs whose window comparison sits a hair on the no-request side of
    θ₁: r_cci = θ₁·r_vpn + h·ε with ε = 1e-3. A float32 prefix-sum window
    (cumsums reach ~4e6, ulp ~0.5) cannot resolve h·ε = 0.024 and flips the
    OFF->WAITING decision; float64 must not."""
    params = small_params(h=h)
    vpn = np.full(T, 1024.0)
    cci = params.theta1 * vpn + 1e-3
    return params, vpn, cci


def test_scan_float64_window_survives_threshold_straddle():
    params, vpn, cci = _straddling_costs()
    from repro.core.costmodel import HourlyCosts

    zeros = np.zeros_like(vpn)
    costs = HourlyCosts(vpn_lease=vpn, vpn_transfer=zeros,
                        cci_lease=cci, cci_transfer=zeros)
    ref = run_togglecci(params, np.zeros_like(vpn), costs=costs)
    assert ref.requests == [], "exact math: never requests"
    # float32 inputs, concrete path: window sums must accumulate in float64.
    out = run_togglecci_scan(
        params, jnp.asarray(vpn, jnp.float32), jnp.asarray(cci, jnp.float32)
    )
    np.testing.assert_array_equal(np.asarray(out["x"]), ref.x)
    assert (np.asarray(out["state"]) == OFF).all()
    # Demonstrate the straddle is real: a float32 prefix-difference window
    # DOES misorder the comparison somewhere in the horizon.
    pref32 = np.cumsum(vpn.astype(np.float32), dtype=np.float32)
    cpref32 = np.cumsum(cci.astype(np.float32), dtype=np.float32)
    t = np.arange(params.h, len(vpn))
    r_vpn32 = pref32[t - 1] - pref32[t - params.h - 1]
    r_cci32 = cpref32[t - 1] - cpref32[t - params.h - 1]
    assert (r_cci32 < params.theta1 * r_vpn32).any(), (
        "float32 windows should flip somewhere (else this regression test "
        "lost its teeth)"
    )


def test_scan_accepts_traceable_toggle_params():
    """ToggleParams fields are array operands: one compiled scan serves
    different (θ, h, D, T_cci) without retracing, and vmaps over them."""
    from repro.core.togglecci import ToggleParams

    d = bursty_trace(horizon=1200, seed=5).sum(axis=1)
    costs = hourly_cost_series(small_params(), d)
    vpn = jnp.asarray(costs.vpn)
    cci = jnp.asarray(costs.cci)

    jit_scan = jax.jit(
        lambda tp, v, c: run_togglecci_scan(tp, v, c)["x"]
    )
    variants = [small_params(), small_params(D=9, T_cci=30, h=48)]
    for p in variants:
        tp = ToggleParams.from_cost_params(p)
        np.testing.assert_array_equal(
            np.asarray(jit_scan(tp, vpn, cci)), run_togglecci(p, d, costs=costs).x
        )

    # vmap over stacked heterogeneous params against broadcast costs.
    tps = ToggleParams(
        theta1=jnp.asarray([p.theta1 for p in variants], jnp.float32),
        theta2=jnp.asarray([p.theta2 for p in variants], jnp.float32),
        h=jnp.asarray([p.h for p in variants], jnp.int32),
        D=jnp.asarray([p.D for p in variants], jnp.int32),
        T_cci=jnp.asarray([p.T_cci for p in variants], jnp.int32),
    )
    xs = jax.vmap(lambda tp: run_togglecci_scan(tp, vpn, cci)["x"])(tps)
    for i, p in enumerate(variants):
        np.testing.assert_array_equal(
            np.asarray(xs[i]), run_togglecci(p, d, costs=costs).x
        )


# ---------------------------------------------------------------------------
# Window reads without a gather
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("form", ["per_row", "per_column"])
@pytest.mark.parametrize("n", [1, 24, 168, 1000])
def test_lag_rows_is_take_along_axis_at_the_clamped_lag(n, form):
    """``lag_rows`` reads ``x[max(0, t - h)]`` bit for bit, one ``h`` per
    row, for lags of none, one, inside the axis, its last index, its whole
    length and past it: a scalar ``h`` under ``vmap`` (the offline plan)
    and a per-column ``h`` on an hour-major block (the chunk), under jit."""
    from repro.core.costmodel import lag_rows

    h = np.array([0, 1, 72, 336, n - 1, n, n + 5], np.int32)
    with jax.enable_x64():
        x = jnp.asarray(np.random.default_rng(n).standard_normal((h.size, n)))
        lo = np.maximum(0, np.arange(n)[None, :] - h[:, None])
        want = jnp.take_along_axis(x, jnp.asarray(lo), axis=1)
        if form == "per_row":
            got = jax.jit(jax.vmap(lag_rows))(x, h)
        else:
            got = jax.jit(lambda a, b: lag_rows(a, b, axis=0))(x.T, h).T
    assert got.dtype == jnp.float64 and got.shape == want.shape
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


def test_plan_window_sums_lower_without_a_gather():
    """The offline plan's window sums at fleet size (2048 links x a year,
    float64) lower to static slices and selects: no ``gather``, which the
    TPU runs one element at a time. Lowering only; nothing runs."""
    from repro.core.togglecci import window_sums

    with jax.enable_x64():
        text = jax.jit(jax.vmap(window_sums)).lower(
            jax.ShapeDtypeStruct((2048, 8760), jnp.float64),
            jax.ShapeDtypeStruct((2048,), jnp.int32),
        ).as_text()
    assert "gather" not in text
    assert "f64" in text
