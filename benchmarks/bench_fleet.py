"""Fleet planning throughput: N heterogeneous links x T hours in ONE jit call.

Measures link-hours/second of the batched engine (``repro.fleet.engine``)
and verifies the acceptance property: the vmapped scan's decision sequences
``x`` match the per-link float64 Python reference bit-for-bit.

CLI:
  python -m benchmarks.bench_fleet                # 128 links x 8760 h
  python -m benchmarks.bench_fleet --smoke        # CI: 16 x 2000, full verify
  python -m benchmarks.bench_fleet --links 512 --verify-links 8
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.fleet.plan import (
    FleetSpec,
    build_fleet_scenario,
    build_report,
    plan_fleet,
    plan_fleet_reference,
)

from ._util import save_rows, use_compile_cache, write_bench_artifact


def run(
    n_links: int = 128,
    horizon: int = 8760,
    *,
    repeats: int = 5,
    verify_links: int | None = None,
    seed: int = 0,
    renew_in_chunks: bool = False,
):
    assert n_links >= 1 and horizon >= 24
    sc = build_fleet_scenario(n_links, horizon=horizon, seed=seed)

    # Stack the fleet and place the demand matrix ONCE, so the timed loop
    # measures pure batched planning — not per-call Python stacking or the
    # host-to-device transfer of the (N, T) demand.
    with jax.enable_x64():
        arrays = sc.fleet.stack(jnp.float64)
        demand = jax.block_until_ready(jnp.asarray(sc.demand, jnp.float64))
    hpm = sc.fleet.hours_per_month

    # Warm-up compiles the single jitted program.
    plan = plan_fleet(
        arrays, demand, hours_per_month=hpm, renew_in_chunks=renew_in_chunks
    )
    jax.block_until_ready(plan["x"])

    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        plan = plan_fleet(
            arrays, demand, hours_per_month=hpm, renew_in_chunks=renew_in_chunks
        )
        jax.block_until_ready(plan["x"])
        times.append(time.perf_counter() - t0)
    best_s = min(times)
    link_hours_per_s = n_links * horizon / best_s

    # Acceptance check: bit-for-bit x against the per-link Python reference
    # on `verify_links` links (None = all of them).
    k = n_links if verify_links is None else min(verify_links, n_links)
    sub = FleetSpec(sc.fleet.links[:k])
    ref = plan_fleet_reference(sub, sc.demand[:k], renew_in_chunks=renew_in_chunks)
    x = np.asarray(plan["x"])[:k]
    exact = bool(np.array_equal(x, ref["x"]))
    assert exact, "batched x diverged from the per-link Python reference"

    rep = build_report(sc, plan)
    t = rep.totals
    rows = [{
        "links": n_links,
        "horizon": horizon,
        "renew_in_chunks": renew_in_chunks,
        "best_s": best_s,
        "link_hours_per_s": link_hours_per_s,
        "verified_links_bitexact": k,
        "fleet_toggle_cost": t["togglecci"],
        "fleet_static_vpn": t["static_vpn"],
        "fleet_static_cci": t["static_cci"],
        "fleet_vs_best_static": t["togglecci"] / t["best_static_per_link"],
        "families": sc.summary(),
    }]
    save_rows("fleet", rows)
    return rows, (
        f"link_hours_per_s={link_hours_per_s:.3g} "
        f"bitexact_links={k}/{n_links}"
    )


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--links", type=int, default=128)
    ap.add_argument("--horizon", type=int, default=8760)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--renew-in-chunks", action="store_true")
    ap.add_argument(
        "--verify-links", type=int, default=None,
        help="links to verify bit-exact vs the Python reference (default all)",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI mode: 16 links x 2000 h, full verification, BENCH artifact",
    )
    args = ap.parse_args()
    if args.smoke:
        args.links, args.horizon, args.repeats = 16, 2000, 2
        args.verify_links = None
    rows, derived = run(
        args.links,
        args.horizon,
        repeats=args.repeats,
        verify_links=args.verify_links,
        seed=args.seed,
        renew_in_chunks=args.renew_in_chunks,
    )
    r = rows[0]
    print(
        f"fleet: {r['links']} links x {r['horizon']} h planned in "
        f"{r['best_s'] * 1e3:.1f} ms -> {r['link_hours_per_s']:.3g} link-hours/s"
    )
    print(derived)
    if args.smoke:
        print("artifact:", write_bench_artifact("fleet", rows))


if __name__ == "__main__":
    main()
