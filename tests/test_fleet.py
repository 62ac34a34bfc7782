"""Fleet subsystem tests: SoA stacking, batched-vs-sequential equivalence,
capacity ceilings, tier-table padding, the report layer, and the offline
plans' int8 decision planes."""
import time

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.core.costmodel import tiered_marginal_cost_tables
from repro.core.pricing import CostParams, TieredRate, flat_rate, make_scenario
from repro.core.togglecci import run_togglecci
from repro.fleet.plan import (
    FleetScenario,
    FleetSpec,
    LinkSpec,
    build_fleet_scenario,
    build_report,
    build_topology_scenario,
    fleet_from_params,
    link_capacity_gb_hr,
    make_policy,
    optimize_routing,
    plan_fleet,
    plan_fleet_reference,
    plan_topology,
    plan_topology_reference,
    replay_plan_topology,
    toggle_events,
)
from repro.fleet.engine import _run_policies
from repro.fleet.spec import PAD_BOUND
from repro.obs import profile

HORIZON = 1600


# ---------------------------------------------------------------------------
# Spec stacking
# ---------------------------------------------------------------------------


def test_stack_shapes_and_tier_padding():
    p_deep = make_scenario("aws", "gcp")            # 4-tier AWS egress
    p_flat = CostParams(1.0, 0.1, 0.02, 0.1, flat_rate(0.1))  # 1-tier
    fleet = fleet_from_params([p_deep, p_flat])
    arr = fleet.stack()
    assert arr.n_links == 2
    K = len(p_deep.vpn_tier.bounds_gb)
    assert arr.tier_bounds.shape == arr.tier_rates.shape == (2, K)
    # Padded rows: bound = PAD_BOUND, rate = 0 -> zero-width, zero-cost.
    np.testing.assert_allclose(np.asarray(arr.tier_bounds)[1, 1:], PAD_BOUND, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(arr.tier_rates)[1, 1:], 0.0)
    assert arr.toggle.D.shape == (2,)


def test_stack_rejects_mixed_billing_calendars():
    a = make_scenario("gcp", "aws")
    b = make_scenario("gcp", "aws", hours_per_month=720)
    with pytest.raises(AssertionError):
        fleet_from_params([a, b])


def test_padded_tier_tables_match_scalar_marginal_cost():
    tiers = [
        TieredRate((100.0, 1000.0, np.inf), (0.12, 0.08, 0.05)),
        flat_rate(0.1),
    ]
    params = [
        CostParams(1.0, 0.1, 0.02, 0.1, t) for t in tiers
    ]
    arr = fleet_from_params(params).stack()
    rng = np.random.default_rng(0)
    start = rng.uniform(0, 2000, size=(2, 64))
    added = rng.uniform(0, 500, size=(2, 64))
    got = np.asarray(
        tiered_marginal_cost_tables(
            jnp.asarray(start, jnp.float32),
            jnp.asarray(added, jnp.float32),
            arr.tier_bounds,
            arr.tier_rates,
        )
    )
    for i, t in enumerate(tiers):
        want = [t.marginal_cost(s, a) for s, a in zip(start[i], added[i])]
        np.testing.assert_allclose(got[i], want, rtol=1e-4)


# ---------------------------------------------------------------------------
# Batched engine == per-link Python reference (the tentpole property)
# ---------------------------------------------------------------------------


@given(seed=st.integers(0, 1000))
@settings(max_examples=3)
def test_batched_matches_sequential_all_families(seed):
    """16 random heterogeneous links x 4 trace families, both renewal
    semantics: the one-jit-call vmapped plan must reproduce the per-link
    float64 Python reference BIT-FOR-BIT on x and state."""
    sc = build_fleet_scenario(16, horizon=HORIZON, seed=seed)
    assert set(sc.summary()) == {"constant", "bursty", "mirage", "puffer"}
    for renew in (False, True):
        plan = plan_fleet(sc.fleet, sc.demand, renew_in_chunks=renew)
        ref = plan_fleet_reference(sc.fleet, sc.demand, renew_in_chunks=renew)
        np.testing.assert_array_equal(np.asarray(plan["x"]), ref["x"])
        np.testing.assert_array_equal(np.asarray(plan["state"]), ref["state"])
        np.testing.assert_allclose(
            np.asarray(plan["toggle_cost"]), ref["toggle_cost"], rtol=1e-9
        )


def test_engine_pallas_tier_path_matches_xla():
    """use_pallas=True must work off-TPU (interpret mode, padded blocks) and
    agree with the XLA tier path to f32 resolution."""
    sc = build_fleet_scenario(4, horizon=700, seed=2)  # 700 % 512 != 0: pads
    ref = plan_fleet(sc.fleet, sc.demand)
    pal = plan_fleet(sc.fleet, sc.demand, use_pallas=True)
    # f32 month-cumulative volumes (~1e5-1e6 GB) resolve tier boundaries to
    # ~0.06 GB, so per-hour costs carry cents-level noise vs the f64 path
    # (same convention as test_kernels' tiered_cost checks): loose absolute
    # tolerance per hour, tight relative on the totals.
    np.testing.assert_allclose(
        np.asarray(pal["vpn_hourly"]), np.asarray(ref["vpn_hourly"]), atol=2e-2
    )
    np.testing.assert_allclose(
        np.asarray(pal["toggle_cost"]), np.asarray(ref["toggle_cost"]), rtol=1e-3
    )


def test_static_cci_pays_provisioning_delay():
    p = CostParams(1.0, 0.1, 0.02, 0.5, flat_rate(0.5), D=10, T_cci=5, h=6)
    fleet = fleet_from_params([p])
    d = np.full((1, 200), 100.0)
    plan = plan_fleet(fleet, d)
    vpn = np.asarray(plan["vpn_hourly"])[0]
    cci = np.asarray(plan["cci_hourly"])[0]
    want = vpn[:10].sum() + cci[10:].sum()
    assert float(plan["static_cci"][0]) == pytest.approx(want, rel=1e-12)


def test_capacity_ceiling_clips_demand():
    p = make_scenario("gcp", "aws")
    cap = 500.0
    fleet = FleetSpec((LinkSpec("l0", p, capacity_gb_hr=cap),))
    d = np.full((1, 400), 10_000.0)   # far above the ceiling
    plan = plan_fleet(fleet, d)
    np.testing.assert_array_equal(np.asarray(plan["demand"])[0], cap)
    # And the reference clips identically.
    ref = plan_fleet_reference(fleet, d)
    np.testing.assert_array_equal(np.asarray(plan["x"]), ref["x"])


def test_heterogeneous_toggle_params_differ_across_links():
    """Two links, identical demand/prices but different thresholds, must
    produce different plans inside ONE batched call (per-link operands)."""
    base = dict(L_cci=2.0, V_cci=0.1, c_cci=0.02, L_vpn=0.1, vpn_tier=flat_rate(0.1))
    eager = CostParams(**base, D=5, T_cci=10, h=10, theta1=0.99, theta2=1.01)
    never = CostParams(**base, D=5, T_cci=10, h=10, theta1=0.01, theta2=100.0)
    fleet = fleet_from_params([eager, never])
    rng = np.random.default_rng(0)
    d = np.tile(rng.uniform(50, 150, size=600), (2, 1))
    plan = plan_fleet(fleet, d)
    x = np.asarray(plan["x"])
    assert x[0].sum() > 0, "aggressive thresholds should activate CCI"
    assert x[1].sum() == 0, "impossible thresholds should never activate"


# ---------------------------------------------------------------------------
# Scenario builder
# ---------------------------------------------------------------------------


def test_scenario_shapes_and_capacity():
    sc = build_fleet_scenario(8, horizon=HORIZON, seed=1)
    assert isinstance(sc, FleetScenario)
    assert sc.demand.shape == (8, HORIZON)
    assert (sc.demand >= 0).all()
    for link in sc.fleet.links:
        assert link.capacity_gb_hr <= link_capacity_gb_hr(10) + 1e-9


def test_link_capacity_is_linksim_calibrated():
    from repro.traffic import linksim

    # Small VLANs bottleneck on the elastic VLAN; big ones on the hard CCI cap.
    assert link_capacity_gb_hr(1) == pytest.approx(1 * 1.7 * 450.0)
    assert link_capacity_gb_hr(10) == pytest.approx(
        linksim.CCI_NOMINAL_GBPS * (1 - linksim.CCI_OVERHEAD) * 450.0
    )


# ---------------------------------------------------------------------------
# Report layer
# ---------------------------------------------------------------------------


def test_toggle_events_match_reference_lists():
    sc = build_fleet_scenario(6, horizon=HORIZON, seed=7)
    plan = plan_fleet(sc.fleet, sc.demand)
    state = np.asarray(plan["state"])
    for i, link in enumerate(sc.fleet.links):
        d = np.minimum(sc.demand[i], link.capacity_gb_hr)
        ref = run_togglecci(link.params, d)
        req, rel = toggle_events(state[i])
        assert list(req) == ref.requests
        assert list(rel) == ref.releases


def test_report_aggregates_and_oracle_bound():
    sc = build_fleet_scenario(6, horizon=HORIZON, seed=11)
    plan = plan_fleet(sc.fleet, sc.demand)
    rep = build_report(sc, plan, include_oracle=True)
    assert len(rep.links) == 6
    t = rep.totals
    assert t["togglecci"] == pytest.approx(
        sum(l.toggle_cost for l in rep.links)
    )
    # OPT lower-bounds every policy, per link and in aggregate.
    for l in rep.links:
        assert l.oracle_cost is not None
        assert l.oracle_cost <= l.toggle_cost * (1 + 1e-9)
        assert l.oracle_cost <= l.best_static * (1 + 1e-9)
    assert "oracle" in t
    text = rep.render_text()
    assert "fleet total" in text and rep.links[0].name in text


# ---------------------------------------------------------------------------
# Offline plans return their decision planes as int8
# ---------------------------------------------------------------------------


def _offline_plan(entry):
    """(plan, reference, policy, demand of the rows) of one offline entry
    point on a small case; the topology references run the FSM on the
    plan's own port series."""
    if entry == "plan_fleet":
        sc = build_fleet_scenario(6, horizon=600, seed=3)
        plan = plan_fleet(sc.fleet, sc.demand)
        with jax.enable_x64():
            policy = make_policy("reactive", sc.fleet.stack(jnp.float64).toggle)
        ref = plan_fleet_reference(sc.fleet, sc.demand)
        return plan, ref, policy, plan["demand"]
    sc = build_topology_scenario(8, n_facilities=2, horizon=600, seed=5)
    routing = optimize_routing(sc.topo, sc.demand)
    with jax.enable_x64():
        arrays = sc.topo.stack(routing, jnp.float64)
        policy = make_policy("reactive", arrays.toggle)
    plan = plan_topology(sc.topo, sc.demand, routing=routing)
    rows = plan["port_demand"]
    if entry == "replay_plan_topology":       # one segment: plan_topology's series
        plan = replay_plan_topology(
            arrays, sc.demand, [(0, arrays.routing)],
            hours_per_month=sc.topo.hours_per_month,
        )
    series = {"vpn": np.asarray(plan["vpn_hourly"]),
              "cci": np.asarray(plan["cci_hourly"])}
    ref = plan_topology_reference(sc.topo, sc.demand, routing, port_costs=series)
    return plan, ref, policy, rows


OFFLINE_ENTRIES = ("plan_fleet", "plan_topology", "replay_plan_topology")


@pytest.mark.parametrize("entry", OFFLINE_ENTRIES)
def test_offline_plan_decisions_are_int8_and_equal_the_reference(entry):
    plan, ref, _, _ = _offline_plan(entry)
    for k in ("x", "state"):
        got = np.asarray(plan[k])
        assert got.dtype == np.int8, k
        assert np.array_equal(got, ref[k]), k
    assert 0 < np.asarray(plan["x"]).mean() < 1, "no row toggled"


@pytest.mark.parametrize("entry", OFFLINE_ENTRIES)
def test_offline_plan_toggle_cost_is_the_wide_scans_bits(entry):
    """``toggle_cost`` is summed from the scan's wide ``x`` before the int8
    cast: bit for bit what the policy scan alone gives on the plan's own
    series, whose planes hold the same values in wider integers."""
    plan, _, policy, rows = _offline_plan(entry)
    with jax.enable_x64():
        wide = jax.jit(_run_policies)(
            policy, rows, plan["vpn_hourly"], plan["cci_hourly"]
        )
    assert np.dtype(wide["x"].dtype).itemsize > 1
    assert np.array_equal(np.asarray(wide["x"]), np.asarray(plan["x"]))
    assert np.array_equal(np.asarray(wide["state"]), np.asarray(plan["state"]))
    got = np.asarray(plan["toggle_cost"])
    assert got.dtype == np.float64
    assert got.tobytes() == np.asarray(wide["total_cost"]).tobytes()


def test_plan_fleet_notes_the_bytes_it_returns_once_per_call():
    n, hours = 5, 300
    sc = build_fleet_scenario(n, horizon=hours, seed=4)
    profile.force_recording(True)
    try:
        t0 = time.perf_counter()
        for _ in range(2):
            plan_fleet(sc.fleet, sc.demand)
        _, counts = profile.recorded(t0)
    finally:
        profile.force_recording(None)
    got = [c for name, _, c in counts if name == "fleet.plan.out_bytes"]
    assert got == [2 * n * hours + 8 * n] * 2     # int8 x and state, float64 cost
