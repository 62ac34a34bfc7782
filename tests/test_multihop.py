"""Multi-hop relay paths and multicast forwarding trees.

The extension contract has three parts:

* **degeneration** — the new row kinds are strict generalizations:
  a :class:`PathSpec` routed 1-hop is BIT-FOR-BIT a :class:`PairSpec`
  (property-tested across all three toggle policies), and a 1-leaf
  multicast group is bit-for-bit the equivalent unicast pair;
* **economics** — on the relay scenario the 2-hop path beats the
  1-hop-only routing by >= 5% (the bench-gated `relay_savings`), the
  forwarding tree beats the per-leaf unicast expansion
  (`tree_sharing_savings`), and `refine_routing` can DISCOVER the relay
  from a 1-hop starting point;
* **streaming** — swapping hop depth mid-stream through
  `FleetRuntime.reroute` / `FleetGateway.reroute` is a pure operand write
  (zero recompiles within the padded leg bound, `ValueError` beyond it)
  and stays decision-bit-exact vs the offline replay oracle.
"""
import dataclasses

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

import repro.fleet.runtime as runtime_mod
from repro.core.pricing import flat_rate
from repro.fleet.plan import (
    build_multicast_scenario,
    build_relay_scenario,
    build_topology_report,
    forecast_topology_policy,
    multicast_unicast_expansion,
    optimize_routing,
    plan_topology,
    refine_routing,
    replay_plan_topology,
)
from repro.fleet.scenario import TopologyScenario
from repro.fleet.stream import FleetRuntime
from repro.fleet.topology import (
    MulticastSpec,
    PairSpec,
    PathSpec,
    PortSpec,
    TopologySpec,
)

PLAN_KEYS = ("x", "state", "toggle_cost", "vpn_hourly", "cci_hourly")


def _assert_plans_equal(a, b, ctx):
    for k in PLAN_KEYS:
        if k in a and k in b:
            np.testing.assert_array_equal(
                np.asarray(a[k]), np.asarray(b[k]), err_msg=f"{ctx}:{k}"
            )


def _demote_paths(topo: TopologySpec) -> TopologySpec:
    """The PairSpec twin: every PathSpec row with its relays stripped."""
    pairs = tuple(
        PairSpec(
            name=p.name, src=p.src, dst=p.dst, L_vpn=p.L_vpn,
            vpn_tier=p.vpn_tier, capacity_gb_hr=p.capacity_gb_hr,
            candidates=p.candidates, family=p.family,
        )
        for p in topo.pairs
    )
    return dataclasses.replace(topo, pairs=pairs)


# ---------------------------------------------------------------------------
# Degeneration properties (hypothesis-driven)
# ---------------------------------------------------------------------------


@settings(max_examples=6)
@given(
    seed=st.integers(0, 7),
    long_gb_hr=st.floats(min_value=50.0, max_value=2500.0),
    policy=st.sampled_from(["reactive", "hysteresis", "forecast"]),
)
def test_one_hop_pathspec_degenerates_to_pairspec(seed, long_gb_hr, policy):
    """A PathSpec topology routed 1-hop plans BIT-FOR-BIT like the PairSpec
    topology with the relays undeclared — under every toggle policy."""
    sc = build_relay_scenario(horizon=240, seed=seed, long_gb_hr=long_gb_hr)
    assert any(getattr(p, "relays", ()) for p in sc.topo.pairs)
    routing = optimize_routing(sc.topo, sc.demand, max_hops=1)
    assert routing.hop_depth == 1

    outs = []
    for topo in (sc.topo, _demote_paths(sc.topo)):
        if policy == "forecast":
            with jax.enable_x64():
                arrays = topo.stack(routing, jnp.float64)
            fpol = forecast_topology_policy(arrays, sc.demand, None, steps=24)
            outs.append(
                plan_topology(topo, sc.demand, routing=routing, policy=fpol)
            )
        else:
            outs.append(plan_topology(
                dataclasses.replace(topo, policy=policy),
                sc.demand, routing=routing,
            ))
    _assert_plans_equal(outs[0], outs[1], f"path-vs-pair[{policy}]")


@settings(max_examples=6)
@given(
    seed=st.integers(0, 7),
    c_a=st.floats(min_value=0.002, max_value=0.05),
    c_b=st.floats(min_value=0.002, max_value=0.05),
)
def test_one_leaf_multicast_degenerates_to_unicast(seed, c_a, c_b):
    """A 1-leaf MulticastSpec is the equivalent PairSpec: no VPN scaling,
    the same tree/port choice, identical planned costs."""
    ports = tuple(
        PortSpec(name=f"p{j}", facility=f"f{j}", cloud="aws",
                 L_cci=4.55, V_cci=0.1, c_cci=c, D=24, T_cci=96, h=72)
        for j, c in enumerate((c_a, c_b))
    )
    tier = flat_rate(0.08)
    group = MulticastSpec(
        name="push", src="gcp-us", leaves=("aws-us",),
        leaf_candidates=((0, 1),), L_vpn=0.105, vpn_tier=tier,
    )
    pair = PairSpec(
        name="push", src="gcp-us", dst="aws-us",
        L_vpn=0.105, vpn_tier=tier, candidates=(0, 1),
    )
    topo_m = TopologySpec(ports=ports, pairs=(), groups=(group,))
    topo_u = TopologySpec(ports=ports, pairs=(pair,))

    rng = np.random.default_rng(seed)
    demand = (200.0 * rng.random((1, 240))).astype(np.float64)

    r_m = optimize_routing(topo_m, demand)
    r_u = optimize_routing(topo_u, demand)
    assert r_m.paths == r_u.paths and len(r_m.paths[0]) == 1
    out_m = plan_topology(topo_m, demand, routing=r_m)
    out_u = plan_topology(topo_u, demand, routing=r_u)
    _assert_plans_equal(out_m, out_u, "1leaf-vs-unicast")


# ---------------------------------------------------------------------------
# Relay / tree economics (the bench-gated numbers)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def relay_sc():
    return build_relay_scenario(horizon=1200, seed=0)


@pytest.fixture(scope="module")
def relay_routing(relay_sc):
    return optimize_routing(relay_sc.topo, relay_sc.demand)


def test_relay_path_beats_direct_by_5pct(relay_sc, relay_routing):
    assert relay_routing.hop_depth >= 2, "the planner must take the relay"
    plan = plan_topology(relay_sc.topo, relay_sc.demand, routing=relay_routing)
    totals = build_topology_report(relay_sc, plan, relay_routing).totals
    assert totals["relay_savings"] >= 0.05, (
        f"relay must save >= 5% vs the 1-hop-only reactive replan, got "
        f"{totals['relay_savings']:.3f}"
    )


def test_refine_routing_discovers_relay_move(relay_sc):
    """Local search started from the best 1-hop routing re-paths the long
    row onto the declared relay (a 'relay' move) and improves cost."""
    direct = optimize_routing(relay_sc.topo, relay_sc.demand, max_hops=1)
    refined, info = refine_routing(
        relay_sc.topo, relay_sc.demand, direct, max_moves=8
    )
    assert info["move_mix"]["relay"] >= 1
    assert refined.hop_depth >= 2
    assert info["cost_after"] < info["cost_before"]


def test_tree_beats_per_leaf_unicast():
    sc = build_multicast_scenario(n_leaves=4, horizon=1200, seed=0)
    routing = optimize_routing(sc.topo, sc.demand)
    (tree_row,) = sc.topo.tree_row_indices()
    assert len(routing.paths[tree_row]) >= 1 and routing.tree_rows == (tree_row,)
    plan = plan_topology(sc.topo, sc.demand, routing=routing)
    totals = build_topology_report(sc, plan, routing).totals
    assert totals["tree_sharing_savings"] > 0.0

    # The report's baseline equals the explicit per-leaf expansion.
    etopo, row_map = multicast_unicast_expansion(sc.topo)
    d_uni = np.asarray(sc.demand)[row_map]
    uni_routing = optimize_routing(etopo, d_uni, max_hops=1)
    uni_plan = plan_topology(etopo, d_uni, routing=uni_routing)
    uni_sc = TopologyScenario(topo=etopo, demand=d_uni, horizon=sc.horizon)
    uni = build_topology_report(uni_sc, uni_plan, uni_routing).totals
    tree_cost = totals["togglecci"]
    assert tree_cost < uni["togglecci"]
    assert abs(
        totals["tree_sharing_savings"] - (1.0 - tree_cost / uni["togglecci"])
    ) < 1e-6


# ---------------------------------------------------------------------------
# Streaming: hop-depth swaps are zero-recompile and replay-exact
# ---------------------------------------------------------------------------


def test_reroute_hop_depth_swap_zero_recompile(relay_sc, relay_routing):
    sc = relay_sc
    direct = optimize_routing(sc.topo, sc.demand, max_hops=1)
    bound = relay_routing.total_hops          # relay plan needs the most legs
    assert bound > direct.total_hops

    rt = FleetRuntime(sc.topo, routing=direct.pad_to(bound))
    T = 240
    for t in range(96):
        rt.step(sc.demand[:, t])
    n_compiled = len(runtime_mod._STEP_CACHE)

    rt.reroute(relay_routing)                 # 1-hop -> 2-hop
    for t in range(96, 168):
        rt.step(sc.demand[:, t])
    rt.reroute(direct)                        # back to 1-hop
    for t in range(168, T):
        rt.step(sc.demand[:, t])
    assert len(runtime_mod._STEP_CACHE) == n_compiled, (
        "hop-depth swaps within the padded leg bound must not recompile"
    )

    # Decision-bit-exactness vs the offline replay oracle.
    with jax.enable_x64():
        arrays = sc.topo.stack(direct.pad_to(bound), jnp.float64)
    replay = replay_plan_topology(
        arrays, sc.demand[:, :T],
        [(0, direct.pad_to(bound)), (96, relay_routing), (168, direct)],
        hours_per_month=sc.topo.hours_per_month,
    )
    rt2 = FleetRuntime(sc.topo, routing=direct.pad_to(bound))
    xs = []
    for t in range(T):
        if t == 96:
            rt2.reroute(relay_routing)
        elif t == 168:
            rt2.reroute(direct)
        xs.append(rt2.step(sc.demand[:, t])["x"])
    np.testing.assert_array_equal(
        np.stack(xs, axis=1), np.asarray(replay["x"])[:, :T]
    )


def test_reroute_beyond_leg_bound_raises(relay_sc, relay_routing):
    direct = optimize_routing(relay_sc.topo, relay_sc.demand, max_hops=1)
    rt = FleetRuntime(relay_sc.topo, routing=direct)   # tight 1-hop bound
    rt.step(relay_sc.demand[:, 0])
    with pytest.raises(ValueError, match="padded bound"):
        rt.reroute(relay_routing)


def test_gateway_multihop_tenant_matches_standalone(relay_sc, relay_routing):
    """A multi-hop tenant streams through the pooled mega-tick bit-for-bit
    like a standalone runtime, including a mid-stream hop-depth reroute —
    with zero extra compiles for the swap."""
    from repro.gateway import FleetGateway, GatewayConfig, TenantSpec
    from repro.gateway.gateway import RuntimeConfig

    sc = relay_sc
    direct = optimize_routing(sc.topo, sc.demand, max_hops=1)
    bound = relay_routing.total_hops
    r0 = direct.pad_to(bound)

    gw = FleetGateway(GatewayConfig(slots_per_bucket=2))
    gw.join("relay", TenantSpec(
        spec=sc.topo, demand=sc.demand, config=RuntimeConfig(routing=r0),
    ))
    ref = FleetRuntime(sc.topo, routing=r0)

    for t in range(48):
        out = gw.tick()["relay"]
        want = ref.step(sc.demand[:, t])
        for k in ("x", "cost"):
            np.testing.assert_array_equal(
                np.asarray(out[k]), np.asarray(want[k]), err_msg=f"t{t}:{k}"
            )
    before = gw.compiles
    gw.reroute("relay", relay_routing)        # hop-depth change, same bound
    ref.reroute(relay_routing)
    assert gw.compiles == before, "pooled reroute must be an operand write"
    for t in range(48, 96):
        out = gw.tick()["relay"]
        want = ref.step(sc.demand[:, t])
        for k in ("x", "cost"):
            np.testing.assert_array_equal(
                np.asarray(out[k]), np.asarray(want[k]), err_msg=f"t{t}:{k}"
            )
