"""Topology-aware planning throughput: P region pairs routed onto M shared
CCI ports, planned in ONE jit call (``repro.fleet.engine.plan_topology``).

Measures pair-hours/second of the routed engine (pair pricing + one-hot
aggregation + the two-level ports x hours vmapped scan), verifies the
per-port decision sequences against the float64 Python reference, and
reports the §VII-A economics: lease-sharing savings vs the PR-1 per-link
planner on the SAME routed (pair, port) choices, and the per-port oracle
gap at a fixed routing. The multi-hop smoke section (on by default) also
times the leg-based engine on a hop-depth-2 relay plan and gates the two
savings claims: relay routing >= 5% cheaper than 1-hop-only on the relay
scenario, and the multicast forwarding tree beats its per-leaf unicast
expansion (``relay_savings_nonneg``, an absolute-floor CI metric).

CLI:
  python -m benchmarks.bench_topology                 # 96 pairs, 4 facilities
  python -m benchmarks.bench_topology --smoke         # CI: 16 x 2000, verify all
  python -m benchmarks.bench_topology --pairs 256 --facilities 8
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.fleet.plan import (
    build_multicast_scenario,
    build_relay_scenario,
    build_topology_report,
    build_topology_scenario,
    optimize_routing,
    plan_topology,
    plan_topology_reference,
)

from ._util import save_rows, use_compile_cache, write_bench_artifact


def _multihop_smoke(repeats: int):
    """Relay + multicast smoke: leg-based engine throughput on a hop-depth-2
    plan, plus the two machine-independent savings claims the gate pins —
    relay routing beats 1-hop-only by >=5% on the relay scenario and the
    forwarding tree beats the per-leaf unicast expansion on the
    broadcast-burst scenario."""
    rsc = build_relay_scenario(horizon=1200, seed=0)
    routing = optimize_routing(rsc.topo, rsc.demand)
    assert routing.hop_depth >= 2, (
        "relay scenario failed to take the relay path"
    )
    hpm = rsc.topo.hours_per_month
    with jax.enable_x64():
        arrays = rsc.topo.stack(routing, jnp.float64)
        demand = jax.block_until_ready(jnp.asarray(rsc.demand, jnp.float64))
    plan = plan_topology(arrays, demand, hours_per_month=hpm)
    jax.block_until_ready(plan["x"])
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        plan = plan_topology(arrays, demand, hours_per_month=hpm)
        jax.block_until_ready(plan["x"])
        times.append(time.perf_counter() - t0)
    n_rows, horizon = rsc.demand.shape
    multihop_phps = n_rows * horizon / min(times)
    relay_savings = build_topology_report(rsc, plan, routing).totals[
        "relay_savings"
    ]

    msc = build_multicast_scenario(n_leaves=4, horizon=1200, seed=0)
    mrouting = optimize_routing(msc.topo, msc.demand)
    mplan = plan_topology(msc.topo, msc.demand, routing=mrouting)
    tree_savings = build_topology_report(msc, mplan, mrouting).totals[
        "tree_sharing_savings"
    ]
    ok = relay_savings >= 0.05 and tree_savings > 0.0
    return multihop_phps, relay_savings, tree_savings, ok


def run(
    n_pairs: int = 96,
    horizon: int = 8760,
    *,
    n_facilities: int = 4,
    ports_per_facility: int = 2,
    repeats: int = 5,
    verify: bool = True,
    include_oracle: bool = False,
    seed: int = 0,
    renew_in_chunks: bool = False,
    multihop: bool = True,
):
    assert n_pairs >= 1 and horizon >= 24
    sc = build_topology_scenario(
        n_pairs,
        n_facilities=n_facilities,
        ports_per_facility=ports_per_facility,
        horizon=horizon,
        seed=seed,
    )
    routing = optimize_routing(sc.topo, sc.demand)

    # Stack + place ONCE so the timed loop measures pure routed planning
    # (the routing matrix is an operand — re-routing would reuse the jit).
    with jax.enable_x64():
        arrays = sc.topo.stack(routing, jnp.float64)
        demand = jax.block_until_ready(jnp.asarray(sc.demand, jnp.float64))
    hpm = sc.topo.hours_per_month

    plan = plan_topology(
        arrays, demand, hours_per_month=hpm, renew_in_chunks=renew_in_chunks
    )
    jax.block_until_ready(plan["x"])

    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        plan = plan_topology(
            arrays, demand, hours_per_month=hpm, renew_in_chunks=renew_in_chunks
        )
        jax.block_until_ready(plan["x"])
        times.append(time.perf_counter() - t0)
    best_s = min(times)
    pair_hours_per_s = n_pairs * horizon / best_s

    if verify:
        # Two-part acceptance check (exactness contract of
        # plan_topology_reference): (1) the FSM property — decisions are
        # bit-for-bit vs the Python FSM run on the engine's OWN port cost
        # series; (2) the aggregation property — the engine's series match
        # the fully independent numpy aggregation to float64 ulp. Comparing
        # decisions across the two aggregations directly would be flaky at
        # scale: summation order differs at ~1e-16 relative, enough to flip
        # a θ comparison that lands within an ulp of equality.
        from repro.fleet.plan import topology_port_costs_reference

        series = {
            "vpn": np.asarray(plan["vpn_hourly"]),
            "cci": np.asarray(plan["cci_hourly"]),
        }
        ref = plan_topology_reference(
            sc.topo, sc.demand, routing,
            renew_in_chunks=renew_in_chunks, port_costs=series,
        )
        assert np.array_equal(np.asarray(plan["x"]), ref["x"]), (
            "batched FSM diverged from the Python reference FSM on "
            "identical port cost series"
        )
        ind = topology_port_costs_reference(sc.topo, sc.demand, routing)
        np.testing.assert_allclose(series["vpn"], ind["vpn"], rtol=1e-12, atol=1e-9)
        np.testing.assert_allclose(series["cci"], ind["cci"], rtol=1e-12, atol=1e-9)

    rep = build_topology_report(
        sc, plan, routing,
        include_oracle=include_oracle,
        renew_in_chunks=renew_in_chunks,
    )
    t = rep.totals
    rows = [{
        "pairs": n_pairs,
        "ports": sc.n_ports,
        "ports_used": rep.ports_used,
        "horizon": horizon,
        "renew_in_chunks": renew_in_chunks,
        "best_s": best_s,
        "pair_hours_per_s": pair_hours_per_s,
        "verified_bitexact": bool(verify),
        "topology_toggle_cost": t["togglecci"],
        "dedicated_per_link_cost": t["dedicated_per_link"],
        "lease_sharing_savings": t["lease_sharing_savings"],
        "oracle_gap": t.get("oracle_gap"),
        "families": sc.summary(),
    }]
    derived = (
        f"pair_hours_per_s={pair_hours_per_s:.3g} "
        f"sharing_savings={100 * t['lease_sharing_savings']:.1f}% "
        f"ports={rep.ports_used}/{sc.n_ports}"
    )
    if multihop:
        mh_phps, relay_savings, tree_savings, ok = _multihop_smoke(repeats)
        rows[0].update({
            "multihop_pair_hours_per_s": mh_phps,
            "relay_savings": relay_savings,
            "tree_sharing_savings": tree_savings,
            # Absolute-floor gate indicator: relay routing saves >= 5% vs
            # 1-hop-only AND the forwarding tree beats per-leaf unicast.
            "relay_savings_nonneg": 1.0 if ok else 0.0,
        })
        derived += (
            f" relay_savings={100 * relay_savings:.1f}% "
            f"tree_savings={100 * tree_savings:.1f}%"
        )
    save_rows("topology", rows)
    return rows, derived


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--pairs", type=int, default=96)
    ap.add_argument("--horizon", type=int, default=8760)
    ap.add_argument("--facilities", type=int, default=4)
    ap.add_argument("--ports-per-facility", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--renew-in-chunks", action="store_true")
    ap.add_argument("--oracle", action="store_true", help="per-port DP column")
    ap.add_argument("--no-verify", action="store_true")
    ap.add_argument(
        "--no-multihop", action="store_true",
        help="skip the relay/multicast smoke section",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI mode: 16 pairs x 2000 h, full verification, BENCH artifact",
    )
    args = ap.parse_args()
    if args.smoke:
        args.pairs, args.horizon, args.repeats = 16, 2000, 2
        args.facilities = 3
    rows, derived = run(
        args.pairs,
        args.horizon,
        n_facilities=args.facilities,
        ports_per_facility=args.ports_per_facility,
        repeats=args.repeats,
        verify=not args.no_verify,
        include_oracle=args.oracle,
        seed=args.seed,
        renew_in_chunks=args.renew_in_chunks,
        multihop=not args.no_multihop,
    )
    r = rows[0]
    print(
        f"topology: {r['pairs']} pairs -> {r['ports_used']}/{r['ports']} ports "
        f"x {r['horizon']} h planned in {r['best_s'] * 1e3:.1f} ms -> "
        f"{r['pair_hours_per_s']:.3g} pair-hours/s"
    )
    print(derived)
    if args.smoke:
        print("artifact:", write_bench_artifact("topology", rows))


if __name__ == "__main__":
    main()
