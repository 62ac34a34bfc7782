"""Toggle-policy comparison: reactive vs SSM-forecast-gated vs hysteresis.

For each demand-trace family (constant / bursty / mirage / puffer) this
builds a multi-pair topology WITH a disjoint demand-history block, routes it
greedily, then plans the same routed portfolio under all three toggle
policies of :mod:`repro.fleet.policy` through the ONE shared
``policy_scan`` kernel — measuring

* planning throughput (pair-hours/s, reactive path — the gated CI metric),
* forecaster training time (off the planning hot path),
* realized cost per policy plus the per-family offline-oracle DP, and
* ``forecast_gain`` — the fraction of the reactive-vs-oracle gap the
  forecast-gated policy closes (the ROADMAP "forecast-driven toggling"
  headline number; positive on sustained-regime families is the
  acceptance bar).

CLI:
  python -m benchmarks.bench_policy                  # 48 pairs x 8760 h/family
  python -m benchmarks.bench_policy --smoke          # CI: 8 x 1200, artifact
  python -m benchmarks.bench_policy --families constant bursty
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.fleet.plan import (
    FAMILIES,
    FAMILY_MARGINS,
    build_topology_report,
    build_topology_scenario,
    forecast_topology_policy,
    make_policy,
    optimize_routing,
    plan_topology,
)

from ._util import save_rows, use_compile_cache, write_bench_artifact


def _timed_plan(arrays, demand, hpm, policy, repeats: int) -> tuple:
    plan = plan_topology(arrays, demand, hours_per_month=hpm, policy=policy)
    jax.block_until_ready(plan["x"])  # warm the jit before timing
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        plan = plan_topology(arrays, demand, hours_per_month=hpm, policy=policy)
        jax.block_until_ready(plan["x"])
        times.append(time.perf_counter() - t0)
    return plan, min(times)


def run(
    n_pairs: int = 48,
    horizon: int = 8760,
    *,
    history_hours: int = 0,
    n_facilities: int = 3,
    ports_per_facility: int = 2,
    repeats: int = 3,
    margin: float = None,
    train_steps: int = 300,
    include_oracle: bool = True,
    families=FAMILIES,
    seed: int = 0,
):
    assert n_pairs >= 1 and horizon >= 24
    history_hours = history_hours or horizon // 2
    fam_rows = []
    total_time = 0.0
    for k, family in enumerate(families):
        sc = build_topology_scenario(
            n_pairs,
            n_facilities=n_facilities,
            ports_per_facility=ports_per_facility,
            horizon=horizon,
            history_hours=history_hours,
            families=(family,),
            seed=seed + k,
        )
        routing = optimize_routing(sc.topo, sc.demand)
        with jax.enable_x64():
            arrays = sc.topo.stack(routing, jnp.float64)
            demand = jax.block_until_ready(jnp.asarray(sc.demand, jnp.float64))
        hpm = sc.topo.hours_per_month

        plan, best_s = _timed_plan(arrays, demand, hpm, None, repeats)
        total_time += best_s

        hyst = make_policy("hysteresis", arrays.toggle)
        hplan, _ = _timed_plan(arrays, demand, hpm, hyst, 1)

        # Per-family confidence margin (ROADMAP: mirage's growth trace
        # over-triggered under the stationary families' margin) — a --margin
        # override applies to every family.
        fam_margin = (
            FAMILY_MARGINS.get(family, 0.05) if margin is None else margin
        )
        t0 = time.perf_counter()
        fpol = forecast_topology_policy(
            arrays, sc.demand, sc.history, margin=fam_margin,
            hours_per_month=hpm, steps=train_steps,
        )
        train_s = time.perf_counter() - t0
        fplan, fbest_s = _timed_plan(arrays, demand, hpm, fpol, repeats)

        rep = build_topology_report(
            sc, plan, routing,
            include_oracle=include_oracle,
            include_dedicated_baseline=False,
            forecast_plan=fplan,
        )
        t = rep.totals
        fam_rows.append({
            "family": family,
            "pairs": n_pairs,
            "ports": sc.n_ports,
            "horizon": horizon,
            "history_hours": history_hours,
            "best_s": best_s,
            "pair_hours_per_s": n_pairs * horizon / best_s,
            "forecast_pair_hours_per_s": n_pairs * horizon / fbest_s,
            "forecaster_train_s": train_s,
            "reactive_cost": t["togglecci"],
            "hysteresis_cost": float(np.sum(np.asarray(hplan["toggle_cost"]))),
            "forecast_cost": t["forecast"],
            "oracle_cost": t.get("oracle"),
            "oracle_gap": t.get("oracle_gap"),
            "forecast_gain": t.get("forecast_gain"),
            "margin": fam_margin,
        })

    gains = {
        r["family"]: r["forecast_gain"]
        for r in fam_rows
        if r["forecast_gain"] is not None and np.isfinite(r["forecast_gain"])
    }
    best_fam = max(gains, key=gains.get) if gains else None
    agg = {
        "family": "all",
        "pairs": n_pairs * len(list(families)),
        "horizon": horizon,
        "pair_hours_per_s": n_pairs * horizon * len(list(families)) / total_time,
        "forecast_gain_best": gains.get(best_fam),
        "forecast_gain_best_family": best_fam,
        "forecast_gain_by_family": gains,
    }
    rows = [agg] + fam_rows
    save_rows("policy", rows)
    derived = (
        f"pair_hours_per_s={agg['pair_hours_per_s']:.3g} "
        + " ".join(f"gain[{f}]={100 * g:+.1f}%" for f, g in gains.items())
    )
    return rows, derived


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--pairs", type=int, default=48)
    ap.add_argument("--horizon", type=int, default=8760)
    ap.add_argument("--history", type=int, default=0, help="0 = horizon/2")
    ap.add_argument("--facilities", type=int, default=3)
    ap.add_argument("--ports-per-facility", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument(
        "--margin", type=float, default=None,
        help="override the per-family FAMILY_MARGINS with one scalar",
    )
    ap.add_argument("--train-steps", type=int, default=300)
    ap.add_argument("--families", nargs="+", default=list(FAMILIES))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-oracle", action="store_true")
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI mode: 8 pairs x 1200 h per family, BENCH artifact",
    )
    args = ap.parse_args()
    if args.smoke:
        args.pairs, args.horizon, args.history = 8, 1200, 600
        args.repeats, args.train_steps = 2, 120
    rows, derived = run(
        args.pairs,
        args.horizon,
        history_hours=args.history,
        n_facilities=args.facilities,
        ports_per_facility=args.ports_per_facility,
        repeats=args.repeats,
        margin=args.margin,
        train_steps=args.train_steps,
        include_oracle=not args.no_oracle,
        families=tuple(args.families),
        seed=args.seed,
    )
    agg = rows[0]
    print(
        f"policy: {agg['pairs']} pairs x {agg['horizon']} h "
        f"-> {agg['pair_hours_per_s']:.3g} pair-hours/s (reactive)"
    )
    for r in rows[1:]:
        g = r["forecast_gain"]
        print(
            f"  {r['family']:<10} reactive ${r['reactive_cost']:.0f}  "
            f"hysteresis ${r['hysteresis_cost']:.0f}  "
            f"forecast ${r['forecast_cost']:.0f}"
            + (f"  oracle ${r['oracle_cost']:.0f}" if r["oracle_cost"] else "")
            + (f"  gain {100 * g:+.1f}%" if g is not None and np.isfinite(g) else "")
        )
    print(derived)
    if args.smoke:
        print("artifact:", write_bench_artifact("policy", rows))


if __name__ == "__main__":
    main()
