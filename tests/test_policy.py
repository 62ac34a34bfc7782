"""Toggle-policy layer tests (the PR's behavior-preservation contract).

The load-bearing property: ``ReactivePolicy`` through the shared
``policy_scan`` kernel reproduces the pre-refactor planners BIT-FOR-BIT —
``run_togglecci`` on random tier tables/delays/demand traces, and the
``plan_fleet`` / ``plan_topology`` decision sequences against their float64
references. Plus: hysteresis degenerates to reactive at hold=1, the
forecast gate's early-fire/suppress mechanics, forecaster training and
causality, spec policy threading, and the pair-move routing refinement.
"""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.core.costmodel import HourlyCosts, hourly_cost_series
from repro.core.pricing import CostParams, TieredRate, flat_rate
from repro.core.togglecci import OFF, ToggleParams, run_togglecci
from repro.fleet.plan import (
    build_fleet_scenario,
    build_topology_report,
    build_topology_scenario,
    forecast_gated_policy,
    hysteresis_policy,
    make_policy,
    optimize_routing,
    plan_fleet,
    plan_fleet_reference,
    plan_topology,
    plan_topology_reference,
    reactive_policy,
    refine_routing,
)
from repro.fleet.policy import policy_scan
from repro.fleet.spec import FleetSpec, LinkSpec, fleet_from_params
from repro.fleet.topology import PairSpec, PortSpec, TopologySpec

HORIZON = 1200


def _random_params(rng: np.random.Generator) -> CostParams:
    """Random pricing + FSM operating point incl. a random ragged tier table."""
    k = int(rng.integers(1, 4))
    bounds = np.sort(rng.uniform(50, 5000, size=k))
    rates = np.sort(rng.uniform(0.02, 0.2, size=k))[::-1]  # decreasing marginal
    tier = TieredRate(tuple(bounds[:-1]) + (np.inf,), tuple(rates))
    return CostParams(
        L_cci=float(rng.uniform(0.5, 8.0)),
        V_cci=float(rng.uniform(0.05, 0.5)),
        c_cci=float(rng.uniform(0.005, 0.05)),
        L_vpn=float(rng.uniform(0.05, 0.5)),
        vpn_tier=tier,
        D=int(rng.integers(0, 40)),
        T_cci=int(rng.integers(1, 80)),
        h=int(rng.integers(1, 80)),
        theta1=float(rng.uniform(0.8, 1.0)),
        theta2=float(rng.uniform(1.0, 1.25)),
    )


def _random_demand(rng: np.random.Generator, T: int) -> np.ndarray:
    """Regime-switching demand so the FSM actually transitions."""
    base = rng.uniform(0, 400)
    d = np.full(T, base)
    for _ in range(int(rng.integers(1, 6))):
        a, b = np.sort(rng.integers(0, T, size=2))
        d[a:b] = rng.uniform(0, 4000)
    return d * rng.uniform(0.8, 1.2, size=T)


# ---------------------------------------------------------------------------
# ReactivePolicy == the paper's FSM, bit-for-bit (the tentpole property)
# ---------------------------------------------------------------------------


@given(seed=st.integers(0, 10_000))
@settings(max_examples=12)
def test_reactive_policy_scan_matches_run_togglecci(seed):
    """Random tier tables, delays, thresholds and demand traces: the shared
    policy_scan kernel with a ReactivePolicy must reproduce the pure-Python
    reference FSM bit-for-bit, in both renewal semantics."""
    rng = np.random.default_rng(seed)
    params = _random_params(rng)
    d = _random_demand(rng, int(rng.integers(50, 700)))
    costs = hourly_cost_series(params, d)
    tp = ToggleParams.from_cost_params(params)
    for renew in (False, True):
        ref = run_togglecci(params, d, costs=costs, renew_in_chunks=renew)
        out = policy_scan(
            reactive_policy(tp, renew_in_chunks=renew),
            jnp.asarray(costs.vpn),
            jnp.asarray(costs.cci),
        )
        np.testing.assert_array_equal(np.asarray(out["x"]), ref.x)
        np.testing.assert_array_equal(np.asarray(out["state"]), ref.state)


@given(seed=st.integers(0, 1000))
@settings(max_examples=2)
def test_reactive_policy_reproduces_plan_fleet(seed):
    """plan_fleet with an EXPLICIT ReactivePolicy == the per-link float64
    reference == plan_fleet with the default policy (all bit-for-bit)."""
    sc = build_fleet_scenario(8, horizon=HORIZON, seed=seed)
    with jax.enable_x64():
        arrays = sc.fleet.stack(jnp.float64)
        pol = reactive_policy(arrays.toggle, renew_in_chunks=False)
    explicit = plan_fleet(arrays, sc.demand, policy=pol,
                          hours_per_month=sc.fleet.hours_per_month)
    default = plan_fleet(sc.fleet, sc.demand)
    ref = plan_fleet_reference(sc.fleet, sc.demand)
    for plan in (explicit, default):
        np.testing.assert_array_equal(np.asarray(plan["x"]), ref["x"])
        np.testing.assert_array_equal(np.asarray(plan["state"]), ref["state"])


@given(seed=st.integers(0, 1000))
@settings(max_examples=2)
def test_reactive_policy_reproduces_plan_topology(seed):
    """plan_topology decision sequences through the policy layer stay
    bit-exact vs the per-port float64 reference FSM on the engine's own
    port cost series (the plan_topology_reference policy contract)."""
    sc = build_topology_scenario(10, n_facilities=3, horizon=HORIZON, seed=seed)
    routing = optimize_routing(sc.topo, sc.demand)
    with jax.enable_x64():
        arrays = sc.topo.stack(routing, jnp.float64)
        pol = reactive_policy(arrays.toggle)
    plan = plan_topology(arrays, sc.demand, policy=pol,
                         hours_per_month=sc.topo.hours_per_month)
    series = {
        "vpn": np.asarray(plan["vpn_hourly"]),
        "cci": np.asarray(plan["cci_hourly"]),
    }
    ref = plan_topology_reference(sc.topo, sc.demand, routing, port_costs=series)
    np.testing.assert_array_equal(np.asarray(plan["x"]), ref["x"])
    np.testing.assert_array_equal(np.asarray(plan["state"]), ref["state"])
    # And the default-policy path is the same compiled program + operands.
    default = plan_topology(sc.topo, sc.demand, routing=routing)
    np.testing.assert_array_equal(np.asarray(default["x"]), ref["x"])


# ---------------------------------------------------------------------------
# HysteresisPolicy
# ---------------------------------------------------------------------------


@given(seed=st.integers(0, 10_000))
@settings(max_examples=6)
def test_hysteresis_hold_one_equals_reactive(seed):
    rng = np.random.default_rng(seed)
    params = _random_params(rng)
    d = _random_demand(rng, 400)
    costs = hourly_cost_series(params, d)
    tp = ToggleParams.from_cost_params(params)
    vpn, cci = jnp.asarray(costs.vpn), jnp.asarray(costs.cci)
    ra = policy_scan(reactive_policy(tp), vpn, cci)
    hy = policy_scan(hysteresis_policy(tp, up_hold=1, down_hold=1), vpn, cci)
    np.testing.assert_array_equal(np.asarray(hy["x"]), np.asarray(ra["x"]))
    np.testing.assert_array_equal(np.asarray(hy["state"]), np.asarray(ra["state"]))


def test_hysteresis_debounces_threshold_chatter():
    """Demand oscillating across breakeven: long holds must cut switches."""
    params = CostParams(2.0, 0.1, 0.02, 0.1, flat_rate(0.1), D=2, T_cci=6, h=4)
    rng = np.random.default_rng(1)
    d = np.where(rng.random(2000) < 0.5, 250.0, 20.0)
    costs = hourly_cost_series(params, d)
    tp = ToggleParams.from_cost_params(params)
    vpn, cci = jnp.asarray(costs.vpn), jnp.asarray(costs.cci)
    switches = lambda out: int(
        np.abs(np.diff(np.asarray(out["x"]))).sum()
    )
    ra = policy_scan(reactive_policy(tp), vpn, cci)
    hy = policy_scan(hysteresis_policy(tp, up_hold=12, down_hold=12), vpn, cci)
    assert switches(hy) < switches(ra)


# ---------------------------------------------------------------------------
# ForecastGatedPolicy mechanics (constructed, deterministic predictions)
# ---------------------------------------------------------------------------


def _step_case():
    """Low demand, then a sustained high regime at t0 — the shape whose
    provisioning delay the forecast gate is built to pre-empt."""
    params = CostParams(2.0, 0.1, 0.02, 0.1, flat_rate(0.1),
                        D=48, T_cci=96, h=96)
    T, t0 = 1500, 600
    d = np.full(T, 10.0)
    d[t0:] = 2000.0
    return params, d


def _true_forward_mean(d: np.ndarray, W: int) -> np.ndarray:
    cs = np.concatenate([[0.0], np.cumsum(d)])
    T = d.shape[0]
    hi = np.minimum(np.arange(T) + W, T)
    return (cs[hi] - cs[np.arange(T)]) / np.maximum(hi - np.arange(T), 1)


def test_forecast_policy_fires_early_on_sustained_regime_shift():
    """With a perfect demand forecast the gated policy must request BEFORE
    the reactive trailing window can react, and end up strictly cheaper."""
    params, d = _step_case()
    costs = hourly_cost_series(params, d)
    tp = ToggleParams.from_cost_params(params)
    W = params.D + params.T_cci
    pred = _true_forward_mean(d, W)
    vpn, cci = jnp.asarray(costs.vpn), jnp.asarray(costs.cci)
    ra = policy_scan(reactive_policy(tp), vpn, cci)
    fo = policy_scan(
        forecast_gated_policy(tp, pred, margin=0.05),
        vpn, cci, demand=jnp.asarray(d),
    )
    first_req = lambda out: int(np.argmax(np.asarray(out["state"]) != OFF))
    assert first_req(fo) < first_req(ra), "forecast must fire earlier"
    assert float(fo["total_cost"]) < float(ra["total_cost"])


def test_forecast_policy_suppresses_transient_spike():
    """A short demand spike trips the reactive request (whole provisioning
    delay + commitment bought for a spike that is shorter than the delay
    itself) — the forecast gate, whose D+T_cci forward-window mean stays
    below the lease breakeven, must suppress it."""
    params = CostParams(2.0, 0.1, 0.02, 0.1, flat_rate(0.1),
                        D=24, T_cci=200, h=12)
    T = 1200
    d = np.full(T, 5.0)
    d[300:315] = 300.0  # 15 h spike < D; window mean stays ~breakeven
    costs = hourly_cost_series(params, d)
    tp = ToggleParams.from_cost_params(params)
    pred = _true_forward_mean(d, params.D + params.T_cci)
    vpn, cci = jnp.asarray(costs.vpn), jnp.asarray(costs.cci)
    ra = policy_scan(reactive_policy(tp), vpn, cci)
    fo = policy_scan(
        forecast_gated_policy(tp, pred, margin=0.05),
        vpn, cci, demand=jnp.asarray(d),
    )
    assert np.asarray(ra["x"]).sum() > 0, "reactive takes the bait"
    assert np.asarray(fo["x"]).sum() == 0, "forecast suppresses the spike"
    assert float(fo["total_cost"]) < float(ra["total_cost"])


def test_forecast_policy_through_plan_fleet():
    """End-to-end: a ForecastGatedPolicy as the vmapped plan_fleet operand
    (per-link pred_demand rows), beating reactive on the step trace."""
    params, d = _step_case()
    fleet = fleet_from_params([params, params])
    demand = np.stack([d, d])
    with jax.enable_x64():
        arrays = fleet.stack(jnp.float64)
        pred = np.stack([
            _true_forward_mean(row, params.D + params.T_cci) for row in demand
        ])
        pol = forecast_gated_policy(arrays.toggle, pred, margin=0.05)
    fplan = plan_fleet(arrays, demand, policy=pol,
                       hours_per_month=fleet.hours_per_month)
    rplan = plan_fleet(fleet, demand)
    assert np.all(
        np.asarray(fplan["toggle_cost"]) < np.asarray(rplan["toggle_cost"])
    )


# ---------------------------------------------------------------------------
# Forecaster training (models/ssm.py demand head)
# ---------------------------------------------------------------------------


def test_forecaster_training_improves_on_persistence():
    from repro.models.ssm import (
        demand_forecaster_apply,
        demand_forecaster_init,
        train_demand_forecaster,
    )

    rng = np.random.default_rng(0)
    t = np.arange(1200)
    series = np.stack([
        50 * (1 + 0.5 * np.sin(2 * np.pi * t / 168)) + rng.normal(0, 2, t.size),
        30 * (1 + t / 1200) + rng.normal(0, 2, t.size),
    ]).clip(min=0.0)
    W = 100
    params, scale = train_demand_forecaster(series, W, steps=200, seed=0)

    u = jnp.log1p(jnp.asarray(series / scale[:, None], jnp.float32))
    cs = np.concatenate([np.zeros((2, 1)), np.cumsum(series / scale[:, None], axis=1)], axis=1)
    T = series.shape[1]
    target = np.log1p((cs[:, W + 1:] - cs[:, 1:T - W + 1]) / W)  # t <= T-W-1
    valid = slice(0, T - W)

    def mse(p):
        y = np.asarray(demand_forecaster_apply(p, u), np.float64)
        return float(np.mean((y[:, valid] - target) ** 2))

    init = demand_forecaster_init(None)
    assert mse(params) < mse(init) * 0.9, (
        "training must beat the persistence init on seasonal/trend series"
    )


def test_forecast_port_demand_is_causal():
    """Perturbing live demand after hour k must not change predictions at
    hours <= k (the forecaster never sees the future)."""
    from repro.fleet.policy import forecast_port_demand

    rng = np.random.default_rng(3)
    hist = rng.uniform(10, 100, size=(3, 300))
    live = rng.uniform(10, 100, size=(3, 200))
    k = 120
    live2 = live.copy()
    live2[:, k:] *= 7.0
    a = forecast_port_demand(hist, live, 50, steps=10, seed=0)
    b = forecast_port_demand(hist, live2, 50, steps=10, seed=0)
    np.testing.assert_array_equal(a[:, : k + 1], b[:, : k + 1])
    assert a.shape == live.shape and (a >= 0).all()


# ---------------------------------------------------------------------------
# Spec threading + factory validation
# ---------------------------------------------------------------------------


def test_spec_policy_threading_and_validation():
    p = CostParams(2.0, 0.1, 0.02, 0.1, flat_rate(0.1), D=3, T_cci=6, h=6)
    link = LinkSpec("l0", p)
    d = np.full((1, 300), 150.0)
    hy = plan_fleet(FleetSpec((link,), policy="hysteresis"), d)
    ra = plan_fleet(FleetSpec((link,)), d)
    assert hy["x"].shape == ra["x"].shape  # same engine, different policy
    with pytest.raises(AssertionError, match="unknown toggle policy"):
        FleetSpec((link,), policy="psychic")
    with pytest.raises(AssertionError, match="unknown toggle policy"):
        TopologySpec(
            ports=(PortSpec("p", "f", "aws", 4.0, 0.1, 0.02),),
            pairs=(PairSpec("a", "gcp", "aws", 0.1, flat_rate(0.1),
                            candidates=(0,)),),
            policy="psychic",
        )
    with pytest.raises(ValueError, match="forecast"):
        make_policy("forecast", ToggleParams.from_cost_params(p))
    with pytest.raises(ValueError, match="unknown"):
        make_policy("psychic", ToggleParams.from_cost_params(p))


# ---------------------------------------------------------------------------
# Routing refinement (pair-move local search)
# ---------------------------------------------------------------------------


def _two_port_topo(c0=0.02, c1=0.02, L0=4.0, L1=4.0):
    mk = lambda n, L, c: PortSpec(
        name=n, facility=f"f-{n}", cloud="aws", L_cci=L, V_cci=0.1, c_cci=c,
        D=6, T_cci=12, h=12,
    )
    pairs = tuple(
        PairSpec(f"pr{i}", "gcp", "aws", 0.105, flat_rate(0.1), candidates=(0, 1))
        for i in range(2)
    )
    return TopologySpec(ports=(mk("p0", L0, c0), mk("p1", L1, c1)), pairs=pairs)


def test_refine_routing_recovers_from_bad_routing():
    """Both pairs parked on the expensive port: the local search must move
    them to the cheap one, replanning only the affected ports, and the
    claimed cost drop must match a full replan."""
    topo = _two_port_topo(c0=0.01, c1=0.2, L0=2.0, L1=20.0)
    rng = np.random.default_rng(0)
    d = rng.uniform(150, 250, size=(2, 600))
    bad = topo.plan([1, 1])
    refined, info = refine_routing(topo, d, bad, max_moves=4)
    assert list(refined.primary) == [0, 0], (
        "both pairs must migrate to the cheap port"
    )
    assert info["cost_after"] < info["cost_before"]
    assert all(m[3] > 0 for m in info["moves"])
    replan = plan_topology(topo, d, routing=refined)
    assert float(np.sum(np.asarray(replan["toggle_cost"]))) == pytest.approx(
        info["cost_after"], rel=1e-9
    )


def test_refine_routing_never_worsens_greedy():
    sc = build_topology_scenario(12, n_facilities=3, horizon=800, seed=4)
    routing = optimize_routing(sc.topo, sc.demand)
    plan = plan_topology(sc.topo, sc.demand, routing=routing)
    refined, info = refine_routing(sc.topo, sc.demand, routing, max_moves=3)
    assert info["cost_after"] <= info["cost_before"] + 1e-6
    # cost_before is the realized plan cost of the input routing.
    assert info["cost_before"] == pytest.approx(
        float(np.sum(np.asarray(plan["toggle_cost"]))), rel=1e-9
    )
    sc.topo.validate_routing(refined)  # moves only within candidate sets


def test_report_forecast_and_refinement_columns():
    sc = build_topology_scenario(
        8, n_facilities=2, horizon=800, history_hours=400,
        families=("bursty",), seed=6,
    )
    routing = optimize_routing(sc.topo, sc.demand)
    plan = plan_topology(sc.topo, sc.demand, routing=routing)
    from repro.fleet.plan import forecast_topology_policy

    with jax.enable_x64():
        arrays = sc.topo.stack(routing, jnp.float64)
    fpol = forecast_topology_policy(arrays, sc.demand, sc.history, steps=60)
    fplan = plan_topology(arrays, sc.demand, policy=fpol,
                          hours_per_month=sc.topo.hours_per_month)
    rep = build_topology_report(
        sc, plan, routing,
        include_oracle=True, forecast_plan=fplan,
        refine=True, refine_max_moves=2,
    )
    t = rep.totals
    assert "forecast" in t and "forecast_gain" in t
    assert "refined_cost" in t and "routing_improvement" in t
    assert t["refined_cost"] <= t["togglecci"] + 1e-6
    assert t["oracle"] <= t["forecast"] * (1 + 1e-9)
    # Per-port column threading.
    assert all(p.forecast_cost is not None for p in rep.ports)
    text = rep.render_text()
    assert "forecast-gated" in text and "refined routing" in text

    # refine must also work when the SPEC's default policy kind is one the
    # engine cannot auto-resolve ("forecast") — the refinement replan is
    # explicitly reactive, compared against the reactive base cost.
    sc2 = dataclasses.replace(
        sc, topo=dataclasses.replace(sc.topo, policy="forecast")
    )
    rep2 = build_topology_report(
        sc2, fplan, routing, include_dedicated_baseline=False,
        refine=True, refine_max_moves=1,
    )
    t2 = rep2.totals
    assert t2["refined_cost"] <= rep2.refine_base_cost + 1e-6
