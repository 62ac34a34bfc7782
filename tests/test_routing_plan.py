"""The typed routing currency: RoutingPlan round-trips + the legacy shim.

Two contracts:

* :class:`repro.fleet.routing.RoutingPlan` is self-consistent — index /
  matrix / operand forms round-trip losslessly, padding and path edits
  preserve identity, and validation rejects malformed plans;
* every public entry point that takes a routing accepts the legacy bare
  forms — ``(P,)`` port indices and ``(M, P)`` one-hot matrices — through
  :func:`repro.fleet.routing.as_routing_plan`, which must WARN
  (``DeprecationWarning`` naming the call site) and produce results
  IDENTICAL to the RoutingPlan spelling (the same shape as the
  ``repro.fleet`` facade shim test).
"""
import re
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.fleet.plan import (
    build_topology_report,
    build_topology_scenario,
    dedicated_fleet,
    optimize_routing,
    plan_topology,
    refine_routing,
    replay_plan_topology,
)
from repro.fleet.routing import RoutingOperand, RoutingPlan, as_routing_plan
from repro.fleet.stream import FleetRuntime


@pytest.fixture(scope="module")
def scenario():
    return build_topology_scenario(6, n_facilities=2, horizon=150, seed=3)


@pytest.fixture(scope="module")
def base_plan(scenario):
    return optimize_routing(scenario.topo, scenario.demand)


# ---------------------------------------------------------------------------
# RoutingPlan construction and round-trips
# ---------------------------------------------------------------------------


def test_from_indices_round_trip():
    idx = np.array([2, 0, 1, 0])
    p = RoutingPlan.from_indices(idx, 3)
    assert p.paths == ((2,), (0,), (1,), (0,))
    assert p.is_unicast and p.hop_depth == 1 and p.total_hops == 4
    np.testing.assert_array_equal(p.primary, idx)
    np.testing.assert_array_equal(p.port_indices(), idx)
    np.testing.assert_array_equal(np.asarray(p), idx)
    # Matrix view is the legacy one-hot; from_matrix round-trips it.
    assert p.matrix.shape == (3, 4)
    np.testing.assert_array_equal(p.matrix.sum(axis=0), np.ones(4))
    p2 = RoutingPlan.from_matrix(p.matrix)
    assert p2.paths == p.paths


def test_operand_round_trip_and_padding():
    p = RoutingPlan(paths=((0,), (1, 2), (0,)), n_ports=3)
    assert p.total_hops == 4 and p.n_legs == 4 and p.hop_depth == 2
    with jax.enable_x64():
        op = p.operand(jnp.float64)
        assert isinstance(op, RoutingOperand)
        back = RoutingPlan.from_operand(op, 3, provenance="rt")
        assert back.paths == p.paths
        # pad_to() only grows the leg bound; decoded paths are unchanged.
        padded = p.pad_to(9)
        assert padded.n_legs == 9 and padded.paths == p.paths
        pop = padded.operand(jnp.float64)
        assert pop.leg_pair.shape == (9,)
        np.testing.assert_array_equal(
            np.asarray(pop.attach_w)[4:], np.zeros(5)
        )
        assert RoutingPlan.from_operand(pop, 3).paths == p.paths
    with pytest.raises(AssertionError):
        p.pad_to(3)  # below the tight bound


def test_replace_path_grows_leg_bound():
    p = RoutingPlan.from_indices([0, 1], 3)
    q = p.replace_path(0, (1, 2))
    assert q.paths == ((1, 2), (1,)) and q.n_legs == 3
    # An already-padded plan keeps its larger bound.
    r = p.pad_to(8).replace_path(0, (1, 2))
    assert r.n_legs == 8


def test_validation_rejects_malformed_plans():
    with pytest.raises(AssertionError, match="out of range"):
        RoutingPlan(paths=((3,),), n_ports=3)
    with pytest.raises(AssertionError, match="twice"):
        RoutingPlan(paths=((1, 1),), n_ports=3)
    with pytest.raises(AssertionError, match="empty"):
        RoutingPlan(paths=((),), n_ports=3)
    with pytest.raises(AssertionError, match="one-hot"):
        RoutingPlan.from_matrix(np.ones((2, 3)))


def test_tree_plan_has_no_index_view():
    p = RoutingPlan(paths=((0,), (1, 2)), n_ports=3, tree_rows=(1,))
    assert not p.is_unicast
    with pytest.raises(TypeError, match="tree rows"):
        p.port_indices()
    # primary still exposes the first hop (obs/actuation mapping).
    np.testing.assert_array_equal(p.primary, [0, 1])


def test_as_routing_plan_passthrough_is_silent(base_plan):
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        got = as_routing_plan(base_plan, n_ports=base_plan.n_ports,
                              context="test")
    assert got is base_plan


# ---------------------------------------------------------------------------
# The legacy shim: every entry point warns AND matches the plan spelling
# ---------------------------------------------------------------------------


def _digest(x):
    """Flatten any result into comparable numpy leaves."""
    if isinstance(x, RoutingPlan):
        return {"paths": x.paths, "tree_rows": x.tree_rows}
    if isinstance(x, dict):
        return {k: _digest(v) for k, v in x.items()}
    if isinstance(x, (tuple, list)):
        return [_digest(v) for v in x]
    if isinstance(x, (jax.Array, np.ndarray)):
        return np.asarray(x)
    return x


def _case_stack(sc, routing):
    with jax.enable_x64():
        op = sc.topo.stack(routing, jnp.float64).routing
    return {f: np.asarray(getattr(op, f)) for f in op._fields}


def _case_plan_topology(sc, routing):
    out = plan_topology(sc.topo, sc.demand, routing=routing)
    return {k: np.asarray(out[k]) for k in ("x", "toggle_cost")}


def _case_replay(sc, routing):
    plan = optimize_routing(sc.topo, sc.demand)
    with jax.enable_x64():
        arrays = sc.topo.stack(plan, jnp.float64)
    out = replay_plan_topology(
        arrays, sc.demand, [(0, routing)],
        hours_per_month=sc.topo.hours_per_month,
    )
    return {k: np.asarray(out[k]) for k in ("x", "toggle_cost")}


def _case_runtime_init(sc, routing):
    rt = FleetRuntime(sc.topo, routing=routing)
    return _digest(rt.step_many(sc.demand[:, :24]))


def _case_runtime_reroute(sc, routing):
    rt = FleetRuntime(sc.topo, routing=optimize_routing(sc.topo, sc.demand))
    rt.step_many(sc.demand[:, :12])
    rt.reroute(routing)
    return _digest(rt.step_many(sc.demand[:, 12:24]))


def _case_report(sc, routing):
    out = plan_topology(sc.topo, sc.demand, routing=routing)
    rep = build_topology_report(sc, {k: np.asarray(v) for k, v in out.items()},
                                routing)
    return rep.totals


def _case_refine(sc, routing):
    refined, info = refine_routing(
        sc.topo, sc.demand, routing, max_moves=2
    )
    return {"paths": refined.paths, "cost": info["cost_after"]}


def _case_dedicated(sc, routing):
    fleet = dedicated_fleet(sc.topo, routing)
    return [(l.name, l.params.L_cci, l.params.c_cci) for l in fleet.links]


CASES = [
    ("TopologySpec.stack", _case_stack),
    ("plan_topology", _case_plan_topology),
    ("replay_plan_topology", _case_replay),
    ("FleetRuntime(routing=)", _case_runtime_init),
    ("FleetRuntime.reroute", _case_runtime_reroute),
    ("build_topology_report", _case_report),
    ("refine_routing", _case_refine),
    ("dedicated_fleet", _case_dedicated),
]


def _assert_same(a, b, ctx=""):
    assert type(a) is type(b) or (
        isinstance(a, (int, float)) and isinstance(b, (int, float))
    ), (ctx, type(a), type(b))
    if isinstance(a, dict):
        assert a.keys() == b.keys(), ctx
        for k in a:
            _assert_same(a[k], b[k], f"{ctx}.{k}")
    elif isinstance(a, np.ndarray):
        np.testing.assert_array_equal(a, b, err_msg=ctx)
    elif isinstance(a, (list, tuple)) and a and not isinstance(a[0], int):
        assert len(a) == len(b), ctx
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_same(x, y, f"{ctx}[{i}]")
    else:
        assert a == b, (ctx, a, b)


@pytest.mark.parametrize("context,case", CASES, ids=[c[0] for c in CASES])
@pytest.mark.parametrize("form", ["indices", "matrix"])
def test_legacy_routing_form_warns_and_matches(
    scenario, base_plan, context, case, form
):
    """Each legacy bare-array spelling: DeprecationWarning naming the call
    site, results identical to the RoutingPlan spelling."""
    legacy = (
        np.asarray(base_plan.primary) if form == "indices"
        else base_plan.matrix
    )
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        want = case(scenario, base_plan)
    with pytest.warns(DeprecationWarning, match=re.escape(context)):
        got = case(scenario, legacy)
    _assert_same(_digest(want), _digest(got), context)


def test_gateway_reroute_legacy_warns_and_matches(scenario, base_plan):
    """FleetGateway.reroute: the pooled-slot operand written through the
    legacy index form equals the RoutingPlan write, and warns."""
    from repro.gateway import FleetGateway, GatewayConfig, TenantSpec
    from repro.gateway.gateway import RuntimeConfig

    def run(routing):
        gw = FleetGateway(GatewayConfig(slots_per_bucket=2))
        gw.join("t", TenantSpec(
            spec=scenario.topo, demand=scenario.demand,
            config=RuntimeConfig(routing=base_plan),
        ))
        gw.tick()
        gw.reroute("t", routing)
        return [np.asarray(gw.tick()["t"]["x"]) for _ in range(3)]

    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        want = run(base_plan)
    with pytest.warns(DeprecationWarning,
                      match=re.escape("FleetGateway.reroute")):
        got = run(np.asarray(base_plan.primary))
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
