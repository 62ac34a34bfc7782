"""Forecast-gated CCI leasing demo: the pluggable toggle-policy layer.

Builds a multi-pair topology on bursty demand WITH a disjoint warm-up
history, trains the tiny SSM demand head (repro.models.ssm) on the
port-aggregated history, and plans the same routed portfolio under all
three toggle policies — reactive (the paper's ToggleCCI), hysteresis
(debounced ablation) and forecast-gated — through the ONE shared
policy_scan kernel. The report's forecast_gain column shows what fraction
of the reactive-vs-oracle gap prediction closes; the refined-routing line
shows what the pair-move local search adds on top of greedy routing.

Run:  PYTHONPATH=src python examples/forecast_demo.py
"""
import numpy as np

import jax
import jax.numpy as jnp

from repro.fleet.plan import (
    build_topology_report,
    build_topology_scenario,
    forecast_topology_policy,
    make_policy,
    optimize_routing,
    plan_topology,
)

N_PAIRS = 24
HORIZON = 3000
HISTORY = 1500  # warm-up hours the forecaster trains on (strictly causal)


def main() -> None:
    sc = build_topology_scenario(
        N_PAIRS,
        n_facilities=3,
        horizon=HORIZON,
        history_hours=HISTORY,
        families=("bursty",),
        seed=7,
    )
    routing = optimize_routing(sc.topo, sc.demand)
    with jax.enable_x64():
        arrays = sc.topo.stack(routing, jnp.float64)
    hpm = sc.topo.hours_per_month
    print(
        f"topology: {N_PAIRS} bursty pairs over {sc.n_ports} candidate ports, "
        f"{HISTORY} h history -> {HORIZON} h horizon"
    )

    # Reactive (the paper's FSM — default policy) and the two alternatives.
    plan = plan_topology(arrays, sc.demand, hours_per_month=hpm)
    hyst = make_policy("hysteresis", arrays.toggle, up_hold=6, down_hold=6)
    hplan = plan_topology(arrays, sc.demand, hours_per_month=hpm, policy=hyst)
    fpol = forecast_topology_policy(arrays, sc.demand, sc.history, margin=0.05)
    fplan = plan_topology(arrays, sc.demand, hours_per_month=hpm, policy=fpol)

    rep = build_topology_report(
        sc, plan, routing,
        include_oracle=True,
        forecast_plan=fplan,
        refine=True,
        refine_max_moves=4,
    )
    print()
    print(rep.render_text(max_rows=8))

    t = rep.totals
    hcost = float(np.sum(np.asarray(hplan["toggle_cost"])))
    print()
    print(f"hysteresis ablation: ${hcost:.0f} "
          f"({100 * (hcost / t['togglecci'] - 1):+.2f}% vs reactive)")
    print("\nper-port forecast gain (gap closed vs offline oracle):")
    for p in rep.ports:
        if p.n_pairs and p.forecast_gain is not None:
            print(
                f"  {p.name:<20} reactive ${p.toggle_cost:>9.0f}  "
                f"forecast ${p.forecast_cost:>9.0f}  "
                f"oracle ${p.oracle_cost:>9.0f}  gain {100 * p.forecast_gain:+.1f}%"
            )


if __name__ == "__main__":
    main()
