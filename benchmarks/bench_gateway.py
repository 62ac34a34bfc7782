"""Multi-tenant gateway throughput: many pooled runtimes, ONE mega-tick.

``bench_runtime`` answers "is per-tick replanning viable for one fleet?";
this bench answers the production question on top of it: can ONE process
front hundreds of independent tenants — each a full ``FleetRuntime``-grade
policy stream — by packing them into a capacity-bucketed state pool and
advancing every tenant one hour per jitted vmapped dispatch? Reported:

* ``tenant_link_steps_per_s`` — the gated CI metric: alive tenants x links
  per tenant x ticks / wall. The mega-tick amortizes the per-dispatch tax
  ``bench_runtime`` measures over the whole pool, so the bar is that the
  POOLED number stays in the same decade as the single-fleet
  ``link_steps_per_s`` at equal total rows — the gateway's host-side
  accounting (per-tenant f64 billing, admission, SLO monitors) must not
  eat the batching win;
* ``tick_us`` (+ p50/p95/p99) — wall per mega-tick across the whole pool
  (every tenant advances one simulated hour per tick). The percentiles are
  computed over STEADY-STATE ticks only: drain-cadence ticks do strictly
  more work by design (ring drain + D2H + per-tenant reconciliation), so
  timing them in the same population turned p99 into a drain detector
  (5075 us vs p50 1125 us at smoke size) instead of a jitter gauge — they
  are reported separately as ``drain_tick_us``;
* ``chunked_tenant_link_steps_per_s`` — the SAME pool advanced K=24 hours
  per dispatch via ``tick_many`` (one chunked mega-tick, drain cadence 72
  = 3 chunks so drains land on chunk boundaries), gated via
  ``extra_metrics``: the pooled chunked path must hold its amortization
  of the per-dispatch tax;
* ``compiles`` — jit-builds of the mega-tick over the WHOLE run incl. a
  post-warm leave/join churn cycle. One capacity bucket compiles exactly
  twice (plain + drain-tick variant); anything larger means tenant churn
  or padding leaked into a traced shape;
* ``zero_recompile_churn`` — absolute-floor-gated indicator (1.0 = a
  tenant leaving and a new tenant joining into the freed slot mid-stream
  triggered ZERO new compiles — the free-list/padding contract);
* ``bit_exact_vs_standalone`` — absolute-floor-gated indicator (1.0 = two
  probe tenants' pooled per-tick outputs, sampled from the SAME timed run,
  equal their own standalone ``FleetRuntime`` streams bit for bit on every
  step field — decisions, window sums, f64 billing);
* ``join_s`` / ``joins_per_s`` — host-side admission cost (pack + pool
  write per tenant), ungated.

CLI:
  python -m benchmarks.bench_gateway           # 256 tenants x 32 links x 400 ticks
  python -m benchmarks.bench_gateway --smoke   # CI: 64 x 16 x 160, artifact
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.fleet.plan import build_fleet_scenario
from repro.fleet.stream import FleetRuntime, RuntimeConfig
from repro.gateway import FleetGateway, GatewayConfig, TenantSpec

from ._util import save_rows, use_compile_cache, write_bench_artifact
from .bench_runtime import _gc_paused

STEP_FIELDS = ("x", "state", "r_vpn", "r_cci", "vpn_cost", "cci_cost", "cost")


def run(n_tenants: int = 256, n_links: int = 32, ticks: int = 400, *,
        cadence: int = 64, seed: int = 0):
    assert n_tenants >= 4 and ticks >= 2 * cadence
    warmup = cadence + 16  # warm BOTH compiled variants (plain + drain)
    horizon = warmup + ticks + 8  # tenants must outlive the churn cycle
    base = build_fleet_scenario(
        n_links, horizon=max(24, horizon), seed=seed
    )

    # One shared spec, per-tenant scaled demand: heterogeneous streams, one
    # capacity bucket — the regime the mega-tick exists for. (Bucket-key
    # heterogeneity is covered by tests; here every tenant must land in the
    # same pool so the compile count isolates churn, not key diversity.)
    def tenant(i: int) -> TenantSpec:
        return TenantSpec(
            spec=base.fleet,
            demand=base.demand * (1.0 + 0.01 * (i % 97)),
            config=RuntimeConfig(),
            horizon=horizon,
        )

    gw = FleetGateway(GatewayConfig(
        slots_per_bucket=n_tenants, queue_limit=n_tenants,
        max_rows=max(4096, n_links), obs=True, cadence=cadence,
    ))
    t0 = time.perf_counter()
    for i in range(n_tenants):
        gw.join(f"t{i:04d}", tenant(i))
    join_s = time.perf_counter() - t0
    assert gw.n_active == n_tenants and gw.n_buckets == 1, (
        gw.n_active, gw.n_buckets
    )

    # Probe tenants for the bit-exactness contract: their pooled outputs
    # are sampled from the SAME ticks being timed (no separate replay).
    probes = {f"t{i:04d}": [] for i in (0, n_tenants - 1)}

    for _ in range(warmup):
        outs = gw.tick()
        for name, got in probes.items():
            got.append(outs[name])
    ticks_s = np.empty(ticks)
    # A tick that ends on the drain cadence does strictly more work (ring
    # drain + D2H + per-tenant metric reconciliation): time it in its own
    # population so the steady-state percentiles measure jitter, not the
    # drain schedule.
    is_drain = (warmup + np.arange(ticks) + 1) % cadence == 0
    with _gc_paused():
        for k in range(ticks):
            t0 = time.perf_counter()
            outs = gw.tick()
            ticks_s[k] = time.perf_counter() - t0
            for name, got in probes.items():
                got.append(outs[name])
    steady_s = ticks_s[~is_drain]
    drain_s = ticks_s[is_drain]
    per_tick = float(ticks_s.mean())  # throughput still pays for drains
    p50, p95, p99 = (float(np.percentile(steady_s, q)) for q in (50, 95, 99))
    drain_tick_us = float(drain_s.mean() * 1e6) if drain_s.size else 0.0
    tenant_link_steps_per_s = n_tenants * n_links / per_tick

    # Churn cycle: one tenant leaves, a fresh one fills the freed slot, the
    # pool ticks on — all against the ALREADY-compiled mega-tick.
    compiles_warm = gw.compiles
    gw.leave("t0001")
    gw.join("fresh", tenant(n_tenants))
    assert gw.handle("fresh").status == "active"
    gw.tick()
    zero_recompile_churn = float(gw.compiles == compiles_warm)
    assert zero_recompile_churn == 1.0, (
        f"churn recompiled the mega-tick: {compiles_warm} -> {gw.compiles}"
    )

    # Bit-exactness: each probe's pooled stream vs its own standalone
    # FleetRuntime over the same hours.
    exact = True
    for name, got in probes.items():
        i = int(name[1:])
        rt = FleetRuntime.from_config(base.fleet, RuntimeConfig())
        dem = base.demand * (1.0 + 0.01 * (i % 97))
        for t, g in enumerate(got):
            want = rt.step(np.ascontiguousarray(dem[:, t]))
            exact = exact and all(
                np.array_equal(np.asarray(g[f]), np.asarray(want[f]))
                for f in STEP_FIELDS
            )
    assert exact, "pooled probe tenants diverged from standalone runtimes"
    violations = gw.check(final=True)
    assert not violations, violations

    # Chunked mega-tick (tick_many): a FRESH pool of the same tenants
    # advanced K=24 hours per dispatch, drain cadence 3 chunks so drains
    # land exactly on chunk boundaries (the chunk-alignment contract).
    # Warm chunks cover both compiled variants (plain + drain) and the
    # ring-population transient; the gated number is the amortized
    # tenant-link-steps/s of the steady chunks.
    chunk_k = 24
    warm_chunks, timed_chunks = 6, 12
    ck_horizon = (warm_chunks + timed_chunks) * chunk_k + 8
    gw2 = FleetGateway(GatewayConfig(
        slots_per_bucket=n_tenants, queue_limit=n_tenants,
        max_rows=max(4096, n_links), obs=True, cadence=3 * chunk_k,
    ))
    base2 = (
        base if base.demand.shape[1] >= ck_horizon
        else build_fleet_scenario(n_links, horizon=ck_horizon, seed=seed)
    )
    for i in range(n_tenants):
        gw2.join(f"t{i:04d}", TenantSpec(
            spec=base2.fleet,
            demand=base2.demand * (1.0 + 0.01 * (i % 97)),
            config=RuntimeConfig(), horizon=ck_horizon,
        ))
    for _ in range(warm_chunks):
        gw2.tick_many(chunk_k)
    chunk_s = np.empty(timed_chunks)
    with _gc_paused():
        for k in range(timed_chunks):
            t0 = time.perf_counter()
            gw2.tick_many(chunk_k)
            chunk_s[k] = time.perf_counter() - t0
    per_chunk = float(chunk_s.mean())
    chunked_tls = n_tenants * n_links * chunk_k / per_chunk

    rows = [{
        "tenants": n_tenants,
        "links_per_tenant": n_links,
        "ticks": ticks,
        "tenant_link_steps_per_s": tenant_link_steps_per_s,
        "tick_us": per_tick * 1e6,
        "tick_us_p50": p50 * 1e6,
        "tick_us_p95": p95 * 1e6,
        "tick_us_p99": p99 * 1e6,
        "drain_tick_us": drain_tick_us,
        "chunk_k": chunk_k,
        "chunk_us": per_chunk * 1e6,
        "chunked_tenant_link_steps_per_s": chunked_tls,
        "compiles": gw.compiles,
        "n_buckets": gw.n_buckets,
        "zero_recompile_churn": zero_recompile_churn,
        "bit_exact_vs_standalone": float(exact),
        "join_s": join_s,
        "joins_per_s": n_tenants / join_s,
    }]
    save_rows("gateway", rows)
    derived = (
        f"tenant_link_steps_per_s={tenant_link_steps_per_s:.3g} "
        f"tick_us={per_tick * 1e6:.1f} "
        f"(steady p50 {p50 * 1e6:.1f} / p95 {p95 * 1e6:.1f} / "
        f"p99 {p99 * 1e6:.1f}; drain {drain_tick_us:.1f}) "
        f"chunked(K={chunk_k})={chunked_tls:.3g}/s "
        f"compiles={gw.compiles} churn_ok={zero_recompile_churn:.0f} "
        f"bit_exact={exact} joins_per_s={rows[0]['joins_per_s']:.1f}"
    )
    return rows, derived


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tenants", type=int, default=256)
    ap.add_argument("--links", type=int, default=32)
    ap.add_argument("--ticks", type=int, default=400)
    ap.add_argument("--cadence", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI mode: 64 tenants x 16 links x 160 ticks, BENCH artifact",
    )
    args = ap.parse_args()
    if args.smoke:
        args.tenants, args.links, args.ticks, args.cadence = 64, 16, 160, 64
    rows, derived = run(
        args.tenants, args.links, args.ticks,
        cadence=args.cadence, seed=args.seed,
    )
    r = rows[0]
    print(
        f"gateway: {r['tenants']} tenants x {r['links_per_tenant']} links "
        f"streamed {r['ticks']} ticks -> "
        f"{r['tenant_link_steps_per_s']:.3g} tenant-link-steps/s "
        f"({r['tick_us']:.1f} us/mega-tick, p99 {r['tick_us_p99']:.1f}; "
        f"{r['compiles']} compiles incl. churn; "
        f"bit-exact vs standalone: {bool(r['bit_exact_vs_standalone'])})"
    )
    print(derived)
    if args.smoke:
        print("artifact:", write_bench_artifact("gateway", rows))


if __name__ == "__main__":
    main()
