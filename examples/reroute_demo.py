"""Live re-routing demo: a hot pair migrates ports MID-STREAM.

The offline topology planner picks one routing for the whole horizon; a
serving system watches demand drift and can re-route while streaming.
This demo runs the `build_reroute_scenario` regime swap (a spill-parked
pair ramps 25x while a hub pair collapses) twice through the SAME streaming
runtime:

* FROZEN   — the greedy day-one routing, never changed;
* LIVE     — every 24 simulated hours the observed trailing-window demand
             means are re-packed with `optimize_routing`; when the packing
             changes, `FleetRuntime.reroute(new_routing)` swaps the routing
             operand mid-stream (no recompile, all window/FSM/billing state
             carried across).

The live run migrates the hot pair onto the hub port once the fading pair
frees capacity headroom — dropping the spill port's lease and its 10x $/GB
premium — and must therefore realize less cost than the frozen run. The
swap is also verified DECISION-BIT-EXACT against the offline
`replay_plan_topology` oracle that applies the same routings at the same
hours (the `reroute()` contract).

Run:  PYTHONPATH=src python examples/reroute_demo.py
"""
import jax
import numpy as np

from repro.fleet.plan import (
    build_reroute_scenario,
    optimize_routing,
    replay_plan_topology,
)
from repro.fleet.stream import FleetRuntime

HORIZON = 2000
SHIFT = 800          # the demand regime swap (unknown to the planner)
OBS_WINDOW = 168     # trailing demand window the live planner watches
REPACK_EVERY = 24    # re-pack cadence, simulated hours


def stream(sc, routing, *, live: bool):
    rt = FleetRuntime(sc.topo, routing=routing)
    cost = 0.0
    swaps = []
    cur = routing  # RoutingPlan
    for t in range(sc.demand.shape[1]):
        if live and t > 0 and t % REPACK_EVERY == 0:
            seen = sc.demand[:, max(0, t - OBS_WINDOW):t]
            r_new = optimize_routing(sc.topo, mean_demand=seen.mean(axis=1))
            if not np.array_equal(r_new.primary, cur.primary):
                rt.reroute(r_new)
                swaps.append((t, cur, r_new))
                cur = r_new
        out = rt.step(sc.demand[:, t])
        cost += float(out["cost"].sum())
    return cost, swaps, rt


def main() -> None:
    sc = build_reroute_scenario(horizon=HORIZON, shift_hour=SHIFT, seed=0)
    r0 = optimize_routing(sc.topo, sc.demand[:, :OBS_WINDOW])
    names = [p.name for p in sc.topo.pairs]
    ports = [p.name for p in sc.topo.ports]
    print(f"pairs {names} over ports {ports}")
    print(f"day-one routing: "
          f"{ {n: ports[m] for n, m in zip(names, r0.primary)} }")

    frozen_cost, _, _ = stream(sc, r0, live=False)
    live_cost, swaps, rt = stream(sc, r0, live=True)

    for t, r_old, r_new in swaps:
        old_i, new_i = r_old.primary, r_new.primary
        moved = [
            f"{names[i]}: {ports[old_i[i]]} -> {ports[new_i[i]]}"
            for i in range(len(names)) if old_i[i] != new_i[i]
        ]
        print(f"hour {t}: re-routed ({'; '.join(moved)})")
    print(f"frozen-routing cost ${frozen_cost:,.0f}  "
          f"live re-routing cost ${live_cost:,.0f}  "
          f"({100 * (1 - live_cost / frozen_cost):+.1f}%)")
    print(f"final port occupancy: "
          f"{dict(zip(ports, rt.port_occupancy().astype(int)))}")

    # The reroute() contract: the streamed decisions equal an offline replay
    # that applies the same routings at the same hours, bit for bit.
    import jax.numpy as jnp

    with jax.enable_x64():
        arrays = sc.topo.stack(r0, jnp.float64)
    schedule = [(0, r0)] + [(t, r_new) for t, _, r_new in swaps]
    replay = replay_plan_topology(
        arrays, sc.demand, schedule, hours_per_month=sc.topo.hours_per_month
    )
    rt2 = FleetRuntime(sc.topo, routing=r0)
    xs = []
    by_hour = {t: r for t, r in schedule if t > 0}
    for t in range(sc.demand.shape[1]):
        if t in by_hour:
            rt2.reroute(by_hour[t])
        xs.append(rt2.step(sc.demand[:, t])["x"])
    exact = np.array_equal(np.stack(xs, axis=1), np.asarray(replay["x"]))
    print(f"streamed reroute decisions == offline replay: {exact}")

    assert swaps, "the live planner must re-route after the regime swap"
    assert live_cost < frozen_cost, "re-routing must beat the frozen routing"
    assert exact, "mid-stream reroute diverged from the offline replay"
    print("OK")


if __name__ == "__main__":
    main()
