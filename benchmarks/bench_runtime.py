"""Streaming-runtime throughput: is per-tick replanning production-viable?

The offline engines amortize one jit dispatch over 8760 hours; a serving
system replans EVERY hour. This bench measures :class:`repro.fleet.runtime.
FleetRuntime` in exactly that regime — N links advanced one hour per jitted
vmapped dispatch, the per-tick outputs synchronously consumed (as an
actuation loop would consume the modes) — and reports

* ``link_steps_per_s``  — the gated CI metric (reactive policy; the
  acceptance bar is ≥ 1e6 on one CPU device: per-tick dispatch overhead,
  not FLOPs, is what could sink it);
* ``tick_us``           — wall per streaming tick (the replanning latency a
  serving loop pays every simulated hour), with ``tick_us_p50/p95/p99``
  tail percentiles (p99 ≫ p50 is the recompile / device-sync smoking gun);
* ``chunked_link_steps_per_s`` — the SAME reactive stream advanced K=24
  hours per dispatch via ``step_many`` (one jitted ``lax.scan`` chunk, one
  packed H2D/D2H per chunk), gated via ``extra_metrics``: the chunked path
  is the tentpole's ≥10x amortization of the per-tick dispatch tax and
  must not regress;
* ``obs_overhead_ratio`` — with-observability CHUNKED streaming throughput
  (device metrics ring + trace + monitors, drain cadence 72 = 3 chunks of
  K=24 so drains land exactly on chunk boundaries) over the COMMITTED
  plain per-tick baseline (``baselines.json["runtime"]``), gated via
  ``extra_metrics``: the acceptance bar is that telemetry-on chunked
  streaming stays above the per-tick throughput of record — turning
  observability on must not take the serving loop below the SLO the
  per-tick gate already enforces. The raw chunked plain-vs-obs same-run
  comparison is also emitted (``obs_vs_plain_ratio``, ``obs_tick_us``)
  ungated, for eyeballing the marginal cost per amortized tick;
* ``forecast_link_steps_per_s`` — same loop under the SSM-forecast-gated
  policy in live mode (carried forecaster state);
* ``topology_port_steps_per_s`` — the SAME streaming loop in topology mode
  at EQUAL port count (M ports == N links; pair demand folded through the
  routing-matrix operand each tick), gated via the ``extra_metrics`` entry
  in ``baselines.json`` — the acceptance bar for the routed-core refactor
  is that shared-port streaming stays within the regression gate of the
  fleet-mode number, and a mid-stream ``reroute()`` (a pure operand swap)
  must not recompile the tick;
* a decision-equality check of the whole streamed horizon against the
  offline ``plan_fleet`` (the tentpole's bit-exactness contract, enforced
  here on bench-sized workloads too).

CLI:
  python -m benchmarks.bench_runtime           # 2048 links x 3000 ticks
  python -m benchmarks.bench_runtime --smoke   # CI: 2048 x 600, artifact
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import time

import numpy as np

import jax

from repro.fleet.plan import (
    build_fleet_scenario,
    build_topology_scenario,
    optimize_routing,
    plan_fleet,
)
from repro.fleet.stream import FleetRuntime, streaming_forecast_policy

from ._util import save_rows, use_compile_cache, write_bench_artifact


@contextlib.contextmanager
def _gc_paused():
    """Collector paused during timed loops (collected once on exit): a GC
    pause landing inside one tick/chunk is allocator noise, not runtime
    cost, and at ~10 timed chunks a single pause moves the mean."""
    on = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if on:
            gc.enable()
            gc.collect()


def _time_stream(rt: FleetRuntime, cols, warmup: int = 20) -> np.ndarray:
    """(ticks,) seconds per tick, steady state (jit warm, per-tick sync
    consume) — keep the whole distribution: p99/p50 separation is the
    drain-cadence / recompile smoking gun a mean would smear away."""
    assert len(cols) > warmup, (len(cols), warmup)
    for t in range(warmup):
        jax.block_until_ready(rt.step(cols[t % len(cols)])["x"])
    out = np.empty(len(cols) - warmup)
    with _gc_paused():
        for i, c in enumerate(cols[warmup:]):
            t0 = time.perf_counter()
            jax.block_until_ready(rt.step(c)["x"])
            out[i] = time.perf_counter() - t0
    return out


def _time_chunked(rt: FleetRuntime, demand: np.ndarray, chunk_k: int,
                  *, warm_chunks: int = 6) -> tuple[np.ndarray, int]:
    """(chunks,) seconds per K-hour ``step_many`` chunk, steady state.

    Outputs come home as host arrays (the replayed f64 snapshot), so wall
    per chunk already includes the packed D2H + host reconciliation. Warm
    chunks cover two full drain windows when obs is on (plain + drain
    chunk variants both compile outside the timed region). Steady state
    for a windowed runtime also means the lookback ring is POPULATED:
    until ``t >= hbuf`` window reads take the early-stream clip branch
    against a still-cold ring — startup transient, not the amortized
    regime this metric gates — so warmup extends to cover the ring."""
    n_chunks = demand.shape[1] // chunk_k
    warm = _chunk_warmup(rt, chunk_k, warm_chunks)
    assert n_chunks > warm, (n_chunks, warm)
    blocks = [
        np.ascontiguousarray(demand[:, i * chunk_k:(i + 1) * chunk_k])
        for i in range(n_chunks)
    ]
    for b in blocks[:warm]:
        rt.step_many(b)
    out = np.empty(n_chunks - warm)
    with _gc_paused():
        for i, b in enumerate(blocks[warm:]):
            t0 = time.perf_counter()
            rt.step_many(b)
            out[i] = time.perf_counter() - t0
    return out, chunk_k


def _chunk_warmup(rt: FleetRuntime, chunk_k: int, warm_chunks: int) -> int:
    """Chunks to warm: the compile floor, extended to ring population."""
    return max(warm_chunks, -(-rt.hbuf // chunk_k))


def run(n_links: int = 1024, ticks: int = 3000, *, history: int = 600, seed: int = 0):
    assert n_links >= 1 and ticks >= 100
    sc = build_fleet_scenario(
        n_links, horizon=ticks, history_hours=history, seed=seed
    )
    cols = [np.ascontiguousarray(sc.demand[:, t]) for t in range(ticks)]

    # Reactive streaming (the gated metric).
    rt = FleetRuntime(sc.fleet)
    ticks_s = _time_stream(rt, cols)
    per_tick = float(ticks_s.mean())
    p50, p95, p99 = (float(np.percentile(ticks_s, q)) for q in (50, 95, 99))

    # Chunked stepping (the tentpole): the same reactive stream advanced
    # K=24 hours per jitted lax.scan dispatch — one packed H2D/D2H per
    # chunk. The gated chunked_link_steps_per_s is the amortized
    # link-steps/s; the acceptance bar is ≥10x the committed per-tick
    # baseline of record.
    chunk_k = 24
    crt = FleetRuntime(sc.fleet)
    chunk_s, _ = _time_chunked(crt, sc.demand, chunk_k)
    per_chunk = float(chunk_s.mean())
    chunk_per_tick = per_chunk / chunk_k
    with open(os.path.join(os.path.dirname(__file__), "baselines.json")) as f:
        committed_tps = float(json.load(f)["runtime"]["value"])

    # Observability on, through the CHUNKED path: drain cadence 72 = 3
    # chunks of K=24, so ring drains land exactly on chunk boundaries (the
    # chunk-alignment contract). The gated obs_overhead_ratio normalizes
    # with-obs chunked throughput against the COMMITTED per-tick baseline
    # — telemetry-on chunked streaming must stay above the per-tick SLO.
    # Warm chunks cover two full drain windows (both compiled variants).
    from repro.obs.observer import ObsConfig

    ort = FleetRuntime(sc.fleet, obs=ObsConfig(cadence=3 * chunk_k))
    obs_chunk_s, _ = _time_chunked(ort, sc.demand, chunk_k)
    obs_per_tick = float(obs_chunk_s.mean()) / chunk_k
    obs_overhead_ratio = (n_links / obs_per_tick) / committed_tps

    # Decision equality vs the offline batch plan on the same horizon.
    rt.reset()
    streamed = rt.run(sc.demand)
    plan = plan_fleet(sc.fleet, sc.demand)
    exact = bool(
        np.array_equal(streamed["x"], np.asarray(plan["x"]))
        and np.array_equal(streamed["state"], np.asarray(plan["state"]))
    )
    assert exact, "streamed decisions diverged from the offline plan"

    # Forecast-gated live mode: SSM state carried through the jitted step.
    import jax.numpy as jnp

    with jax.enable_x64():
        arrays = sc.fleet.stack(jnp.float64)
    t0 = time.perf_counter()
    pol, fc = streaming_forecast_policy(
        arrays, sc.history, steps=60, hours_per_month=sc.fleet.hours_per_month
    )
    train_s = time.perf_counter() - t0
    frt = FleetRuntime(
        arrays, policy=pol, forecaster=fc,
        hours_per_month=sc.fleet.hours_per_month,
    )
    f_per_tick = float(_time_stream(frt, cols).mean())

    # Topology mode at EQUAL port count: M ≈ n_links ports sharing leases
    # over P = M pairs, the routing matrix a per-tick traced operand
    # (rounded down to the facility granularity for odd --links values).
    n_eq = 2 * max(1, n_links // 2)
    tsc = build_topology_scenario(
        n_eq, n_facilities=max(1, n_eq // 2), ports_per_facility=2,
        horizon=ticks, seed=seed,
    )
    routing = optimize_routing(tsc.topo, tsc.demand)
    trt = FleetRuntime(tsc.topo, routing=routing)
    assert trt.n_rows == n_eq, (trt.n_rows, n_eq)
    tcols = [np.ascontiguousarray(tsc.demand[:, t]) for t in range(ticks)]
    t_per_tick = float(_time_stream(trt, tcols).mean())
    # A live reroute is a pure operand swap: the next tick must reuse the
    # compiled step (measured as one tick, not a recompile pause).
    trt.reroute(routing)
    t0 = time.perf_counter()
    jax.block_until_ready(trt.step(tcols[0])["x"])
    reroute_tick_s = time.perf_counter() - t0
    assert reroute_tick_s < max(50 * t_per_tick, 0.25), (
        f"post-reroute tick took {reroute_tick_s:.3f}s — the routing swap "
        "must not trigger a recompile"
    )

    rows = [{
        "links": n_links,
        "ticks": ticks,
        "link_steps_per_s": n_links / per_tick,
        "tick_us": per_tick * 1e6,
        "tick_us_p50": p50 * 1e6,
        "tick_us_p95": p95 * 1e6,
        "tick_us_p99": p99 * 1e6,
        "chunk_k": chunk_k,
        "chunked_link_steps_per_s": n_links / chunk_per_tick,
        "chunk_us": per_chunk * 1e6,
        "chunked_speedup_vs_per_tick": per_tick / chunk_per_tick,
        "obs_link_steps_per_s": n_links / obs_per_tick,
        "obs_tick_us": obs_per_tick * 1e6,
        "obs_overhead_ratio": obs_overhead_ratio,
        "obs_vs_plain_ratio": chunk_per_tick / obs_per_tick,
        "forecast_link_steps_per_s": n_links / f_per_tick,
        "forecast_tick_us": f_per_tick * 1e6,
        "forecaster_train_s": train_s,
        "bit_exact_vs_offline": exact,
        "topology_ports": trt.n_rows,
        "topology_pairs": trt.n_demand_rows,
        "topology_port_steps_per_s": trt.n_rows / t_per_tick,
        "topology_tick_us": t_per_tick * 1e6,
        "reroute_tick_us": reroute_tick_s * 1e6,
    }]
    save_rows("runtime", rows)
    derived = (
        f"link_steps_per_s={rows[0]['link_steps_per_s']:.3g} "
        f"tick_us={rows[0]['tick_us']:.1f} "
        f"(p50 {rows[0]['tick_us_p50']:.1f} / p95 {rows[0]['tick_us_p95']:.1f}"
        f" / p99 {rows[0]['tick_us_p99']:.1f}) "
        f"chunked(K={chunk_k})={rows[0]['chunked_link_steps_per_s']:.3g}/s "
        f"({rows[0]['chunked_speedup_vs_per_tick']:.1f}x per-tick) "
        f"obs_ratio={rows[0]['obs_overhead_ratio']:.3f} "
        f"forecast={rows[0]['forecast_link_steps_per_s']:.3g}/s "
        f"topology={rows[0]['topology_port_steps_per_s']:.3g}/s"
    )
    return rows, derived


def run_ksweep(n_links: int = 2048, ticks: int = 3000, *, seed: int = 0,
               ks=(1, 6, 24, 168)):
    """Nightly K-sweep: chunked streaming throughput vs chunk length.

    One fresh reactive runtime per K over the same scenario; emits one row
    per K (uploaded as the ``runtime_ksweep`` artifact)."""
    sc = build_fleet_scenario(n_links, horizon=ticks, seed=seed)
    rows = []
    for k in ks:
        rt = FleetRuntime(sc.fleet)
        warm = _chunk_warmup(rt, k, 6 if ticks // k > 8 else 2)
        assert ticks // k > warm, (
            f"--ticks {ticks} too short for K={k} (need > {warm} chunks)"
        )
        chunk_s, _ = _time_chunked(rt, sc.demand, k, warm_chunks=warm)
        per_tick = float(chunk_s.mean()) / k
        rows.append({
            "links": n_links,
            "chunk_k": k,
            "chunks_timed": len(chunk_s),
            "chunk_us": float(chunk_s.mean()) * 1e6,
            "chunked_link_steps_per_s": n_links / per_tick,
        })
        print(
            f"ksweep: K={k:>4} -> {rows[-1]['chunked_link_steps_per_s']:.3g} "
            f"link-steps/s ({rows[-1]['chunk_us']:.0f} us/chunk)"
        )
    save_rows("runtime_ksweep", rows)
    return rows


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--links", type=int, default=2048)
    ap.add_argument("--ticks", type=int, default=3000)
    ap.add_argument("--history", type=int, default=600)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI mode: 2048 links x 600 ticks, BENCH artifact",
    )
    ap.add_argument(
        "--ksweep", action="store_true",
        help="nightly mode: chunk-length sweep (K=1/6/24/168), artifact only",
    )
    args = ap.parse_args()
    if args.ksweep:
        # Sweep table only (results/bench/, uploaded by the nightly job) —
        # no BENCH_*.json: the sweep is a curve for drift inspection, not a
        # gated bench, and the gate rejects unlisted BENCH artifacts.
        run_ksweep(args.links, args.ticks, seed=args.seed)
        print("artifact: results/bench/runtime_ksweep.json")
        return
    if args.smoke:
        args.links, args.ticks, args.history = 2048, 600, 300
    rows, derived = run(
        args.links, args.ticks, history=args.history, seed=args.seed
    )
    r = rows[0]
    print(
        f"runtime: {r['links']} links streamed {r['ticks']} ticks -> "
        f"{r['link_steps_per_s']:.3g} link-steps/s "
        f"({r['tick_us']:.1f} us/tick, p99 {r['tick_us_p99']:.1f}; "
        f"chunked K={r['chunk_k']}: {r['chunked_link_steps_per_s']:.3g}/s; "
        f"obs ratio {r['obs_overhead_ratio']:.3f}; forecast-gated "
        f"{r['forecast_link_steps_per_s']:.3g}/s; topology mode "
        f"{r['topology_port_steps_per_s']:.3g} port-steps/s at "
        f"{r['topology_ports']} ports / {r['topology_pairs']} pairs), "
        f"bit-exact vs offline: {r['bit_exact_vs_offline']}"
    )
    print(derived)
    if args.smoke:
        print("artifact:", write_bench_artifact("runtime", rows))


if __name__ == "__main__":
    main()
