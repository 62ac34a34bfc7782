"""Chip smoke test: drive the controller's main path once on one TPU chip.

Every phase goes through the entry points an operator calls, at a size an
operator would call real, and checks its results by the repository's own
means:

* ``kernels``   — the three Pallas tiered-cost kernels, compiled (never
  interpreted) at 2048 rows, against their XLA twins; and the fleet
  engine's ``use_pallas`` pricing stage against the same formula in XLA.
* ``offline``   — ``plan_fleet`` on a 2048-link, 8760-hour fleet: decisions
  equal to the float64 numpy reference on every link, toggle cost to a
  relative 1e-9.
* ``topology``  — ``plan_topology`` on the full-size topology scenario
  (96 pairs, 4 facilities, 8760 h) and on the relay and multicast
  scenarios, each with the two-part check of ``benchmarks/bench_topology``.
* ``stream``    — ``FleetRuntime`` on the same 2048-link fleet: 168
  per-tick ``step()`` calls, then ``step_many`` in chunks of 24 to the end
  of the year, decisions equal to the offline plan bit for bit; in topology
  mode a mid-stream ``reroute()`` compiles nothing and matches the offline
  replay.
* ``gateway``   — ``FleetGateway`` with 64 tenants x 32 links advanced
  ``tick_many(24)`` for 30 days: probe tenants equal their standalone
  runtimes bit for bit, exactly two compiles per capacity bucket.

Each phase prints one line with its compile time apart from its steady
time and the device's ``peak_bytes_in_use``. The last line of standard
output is ``{"ok": true, "device": {...}}`` only when every phase passed.
There is no CPU path: without a TPU the script exits non-zero.

Run from the repository root:  python chip_smoke.py [--only PHASE,...]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

PHASES = ("kernels", "offline", "topology", "stream", "gateway")
SEED = 0
N_LINKS, HORIZON, HPM = 2048, 8760, 730
CHUNK_K, PER_TICK = 24, 168
STEP_FIELDS = ("x", "state", "r_vpn", "r_cci", "vpn_cost", "cci_cost", "cost")


class CompileLog:
    """Counts compiles and sums their seconds through ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.names = []     # compiled programs, in order
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, fun_name="", **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.names.append(fun_name)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.compiles, self.cache_hits


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _timed(fn):
    """(result, seconds) of ``fn()`` with every output array ready."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _rel_err(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(float(np.abs(want).max()), 1e-12))


def _first_mismatch(got, want) -> str:
    import numpy as np

    bad = np.argwhere(np.asarray(got) != np.asarray(want))
    rows = np.unique(bad[:, 0]) if bad.size else []
    return f"{len(rows)} rows differ, first at {tuple(bad[0])}" if bad.size else ""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# --------------------------------------------------------------------------
# Phases. Each returns a dict of numbers for its report line; the first and
# steady calls of every program are timed apart.
# --------------------------------------------------------------------------


def phase_kernels(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.costmodel import monthly_cumsum
    from repro.core.pricing import AWS_EGRESS_INTERNET
    from repro.fleet import engine
    from repro.kernels import ref
    from repro.kernels import tiered_cost as tc

    rng = np.random.default_rng(SEED)
    N, T, Kt = N_LINKS, HORIZON, 4
    d = jnp.asarray(rng.lognormal(4.0, 1.0, (N, T)), jnp.float32)
    cum = jax.jit(monthly_cumsum, static_argnums=1)(d, HPM)
    b = np.sort(rng.uniform(1e3, 5e5, (N, Kt)), axis=1)
    b[:, -1] = 1e30
    bounds = jnp.asarray(b, jnp.float32)
    rates = jnp.asarray(
        np.sort(rng.uniform(0.01, 0.12, (N, Kt)), axis=1)[:, ::-1].copy(),
        jnp.float32,
    )
    out = {}

    def both(name, kernel, twin, *args):
        got, first = _timed(lambda: kernel(*args))
        _, steady = _timed(lambda: kernel(*args))
        want = twin(*args)
        out[f"{name}_first_s"], out[f"{name}_steady_s"] = first, steady
        return got, want

    batched = jax.jit(tc.tiered_cost_batched)
    got, want = both("batched", batched, jax.jit(tc.tiered_cost_batched_ref),
                     cum, d, bounds, rates)
    out["batched_rel_err"] = err = _rel_err(got, want)
    check(err < 1e-5, f"tiered_cost_batched vs XLA twin: rel err {err:.2e}")

    tier = AWS_EGRESS_INTERNET
    tb = jnp.asarray([x if np.isfinite(x) else 1e30 for x in tier.bounds_gb],
                     jnp.float32)
    tr = jnp.asarray(tier.rates, jnp.float32)
    static = jax.jit(lambda c, dd: tc.tiered_cost(c, dd, tier.bounds_gb, tier.rates))
    twin = jax.jit(lambda c, dd: ref.tiered_cost(c, dd, tb, tr))
    got, want = both("static", static, twin, cum.T, d.T)
    out["static_rel_err"] = err = _rel_err(got, want)
    check(err < 1e-5, f"tiered_cost vs XLA twin: rel err {err:.2e}")

    reset = jnp.asarray(np.arange(CHUNK_K) == CHUNK_K // 2, jnp.int32)
    d_chunk = d[:, :CHUNK_K]
    scan = jax.jit(tc.tiered_cost_scan)
    (got, got_cum), (want, want_cum) = both(
        "scan", scan, jax.jit(tc.tiered_cost_scan_ref),
        cum[:, 0], d_chunk, bounds, rates, reset,
    )
    out["scan_rel_err"] = err = max(_rel_err(got, want), _rel_err(got_cum, want_cum))
    check(err < 1e-5, f"tiered_cost_scan vs XLA twin: rel err {err:.2e}")
    h = CHUNK_K // 2
    a, cum_a = scan(cum[:, 0], d_chunk[:, :h], bounds, rates, reset[:h])
    c, _ = scan(cum_a, d_chunk[:, h:], bounds, rates, reset[h:])
    check(np.array_equal(np.concatenate([np.asarray(a), np.asarray(c)], 1),
                         np.asarray(got)),
          "tiered_cost_scan: two chained half-chunks differ from one chunk")

    # The fleet engine's Pallas pricing stage on a real fleet, against the
    # twin in f32 (as the kernel computes) on the engine's own volumes.
    sc = ctx["scenario"]()
    with jax.enable_x64():
        arrays = sc.fleet.stack(jnp.float64)
        demand = jnp.asarray(sc.demand, jnp.float64)
        stage = jax.jit(lambda a, dd: engine.routed_cost_series(
            a, dd, hours_per_month=HPM, use_pallas=True).vpn)
        got, first = _timed(lambda: stage(arrays, demand))
        _, steady = _timed(lambda: stage(arrays, demand))

        def volumes(a, dd):
            dc = jnp.minimum(dd, a.capacity[:, None])
            return monthly_cumsum(dc, HPM), dc

        mc, dc = jax.jit(volumes)(arrays, demand)
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    tier = jax.jit(tc.tiered_cost_batched_ref)(
        f32(mc), f32(dc), f32(arrays.tier_bounds), f32(arrays.tier_rates))
    want = np.asarray(arrays.L_vpn)[:, None] + np.asarray(tier, np.float64)
    out["engine_stage_first_s"], out["engine_stage_steady_s"] = first, steady
    out["engine_stage_rel_err"] = err = _rel_err(got, want)
    check(err < 1e-5, f"engine use_pallas stage vs XLA twin: rel err {err:.2e}")
    return out


def phase_offline(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.fleet.plan import plan_fleet, plan_fleet_reference

    sc = ctx["scenario"]()
    with jax.enable_x64():
        arrays = sc.fleet.stack(jnp.float64)
        demand = jax.device_put(np.asarray(sc.demand, np.float64))
    run = lambda: plan_fleet(arrays, demand, hours_per_month=HPM)
    plan, first = _timed(run)
    plan, steady = _timed(run)
    x, state = np.asarray(plan["x"]), np.asarray(plan["state"])
    toggle = np.asarray(plan["toggle_cost"])
    del plan
    ctx["offline"] = {"x": x, "state": state}
    t0 = time.perf_counter()
    want = plan_fleet_reference(sc.fleet, sc.demand)
    verify_s = time.perf_counter() - t0
    check(np.array_equal(x, want["x"]), "plan_fleet x vs reference: "
          + _first_mismatch(x, want["x"]))
    check(np.array_equal(state, want["state"]), "plan_fleet state vs reference: "
          + _first_mismatch(state, want["state"]))
    err = float(np.max(np.abs(toggle - want["toggle_cost"])
                       / np.abs(want["toggle_cost"])))
    check(err <= 1e-9, f"plan_fleet toggle_cost vs reference: rel err {err:.2e}")
    return {
        "links": N_LINKS, "hours": HORIZON, "first_s": first, "steady_s": steady,
        "link_hours_per_s": N_LINKS * HORIZON / steady, "verify_s": verify_s,
        "toggle_cost_rel_err": err,
    }


def _plan_and_verify(sc, name, out):
    """``bench_topology``'s two-part check on one topology scenario."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.fleet.plan import (
        optimize_routing,
        plan_topology,
        plan_topology_reference,
        topology_port_costs_reference,
    )

    routing = optimize_routing(sc.topo, sc.demand)
    hpm = sc.topo.hours_per_month
    with jax.enable_x64():
        arrays = sc.topo.stack(routing, jnp.float64)
        demand = jax.device_put(np.asarray(sc.demand, np.float64))
    run = lambda: plan_topology(arrays, demand, hours_per_month=hpm)
    plan, first = _timed(run)
    plan, steady = _timed(run)
    series = {"vpn": np.asarray(plan["vpn_hourly"]),
              "cci": np.asarray(plan["cci_hourly"])}
    x = np.asarray(plan["x"])
    ref = plan_topology_reference(sc.topo, sc.demand, routing, port_costs=series)
    check(np.array_equal(x, ref["x"]),
          f"{name}: FSM vs reference on the engine's port series: "
          + _first_mismatch(x, ref["x"]))
    ind = topology_port_costs_reference(sc.topo, sc.demand, routing)
    for k in ("vpn", "cci"):
        np.testing.assert_allclose(series[k], ind[k], rtol=1e-12, atol=1e-9,
                                   err_msg=f"{name}: {k} port series")
    P, T = sc.demand.shape
    out.update({f"{name}_first_s": first, f"{name}_steady_s": steady,
                f"{name}_pair_hours_per_s": P * T / steady})
    return routing, x


def _topology_scenario():
    """``bench_topology``'s full-size scenario and its optimized routing."""
    from repro.fleet.plan import build_topology_scenario, optimize_routing

    tsc = build_topology_scenario(96, n_facilities=4, ports_per_facility=2,
                                  horizon=HORIZON, seed=SEED)
    return tsc, optimize_routing(tsc.topo, tsc.demand)


def phase_topology(ctx):
    from repro.fleet.plan import build_multicast_scenario, build_relay_scenario

    out = {}
    tsc, _ = ctx["topology"] = _topology_scenario()
    _plan_and_verify(tsc, "topo96", out)
    rsc = build_relay_scenario(seed=SEED)
    relay, _ = _plan_and_verify(rsc, "relay", out)
    check(relay.hop_depth >= 2, "relay scenario did not take the relay path")
    _plan_and_verify(build_multicast_scenario(seed=SEED), "multicast", out)
    return out


def phase_stream(ctx, log):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.fleet.plan import optimize_routing, replay_plan_topology
    from repro.fleet.stream import FleetRuntime

    sc = ctx["scenario"]()
    if "offline" not in ctx:
        phase_offline(ctx)
    want = ctx["offline"]
    rt = FleetRuntime(sc.fleet)
    demand = np.asarray(sc.demand, np.float64)
    xs, states = [], []
    tick_s = []
    for t in range(PER_TICK):
        t0 = time.perf_counter()
        o = rt.step(demand[:, t])
        tick_s.append(time.perf_counter() - t0)
        xs.append(o["x"][:, None])
        states.append(o["state"][:, None])
    chunk_s = []
    for t in range(PER_TICK, HORIZON, CHUNK_K):
        t0 = time.perf_counter()
        o = rt.step_many(demand[:, t:t + CHUNK_K])
        chunk_s.append(time.perf_counter() - t0)
        xs.append(o["x"])
        states.append(o["state"])
    x, state = np.concatenate(xs, 1), np.concatenate(states, 1)
    check(np.array_equal(x, want["x"]),
          "streamed x vs offline plan: " + _first_mismatch(x, want["x"]))
    check(np.array_equal(state, want["state"]),
          "streamed state vs offline plan: " + _first_mismatch(state, want["state"]))

    # Topology mode: a mid-stream reroute is an operand swap; the compiled
    # tick is reused (eager conversions of the new operand may compile).
    tsc, routing = ctx.get("topology") or _topology_scenario()
    trt = FleetRuntime(tsc.topo, routing=routing)
    td = np.asarray(tsc.demand, np.float64)
    t_swap, t_end = PER_TICK, PER_TICK + CHUNK_K
    txs = [trt.step(td[:, t])["x"] for t in range(t_swap)]
    swapped = optimize_routing(tsc.topo, td[:, :t_swap])
    seen = len(log.names)
    trt.reroute(swapped)
    txs += [trt.step(td[:, t])["x"] for t in range(t_swap, t_end)]
    tick_recompiles = log.names[seen:].count("jit(step_many)")
    check(tick_recompiles == 0, f"reroute() recompiled the tick {tick_recompiles}x")
    with jax.enable_x64():
        arrays = tsc.topo.stack(routing, jnp.float64)
    replay = replay_plan_topology(arrays, td[:, :t_end],
                                  [(0, routing), (t_swap, swapped)],
                                  hours_per_month=tsc.topo.hours_per_month)
    tx = np.stack(txs, 1)
    check(np.array_equal(tx, np.asarray(replay["x"])),
          "rerouted stream vs offline replay: "
          + _first_mismatch(tx, np.asarray(replay["x"])))
    tick_s, chunk_s = np.asarray(tick_s), np.asarray(chunk_s)
    return {
        "links": N_LINKS,
        "tick_first_s": tick_s[0], "tick_steady_s": float(tick_s[1:].mean()),
        "tick_p50_s": float(np.percentile(tick_s[1:], 50)),
        "tick_p99_s": float(np.percentile(tick_s[1:], 99)),
        "chunk_first_s": chunk_s[0], "chunk_steady_s": float(chunk_s[1:].mean()),
        "chunk_p99_s": float(np.percentile(chunk_s[1:], 99)),
        "chunked_link_steps_per_s": N_LINKS * CHUNK_K / float(chunk_s[1:].mean()),
        "reroute_tick_recompiles": tick_recompiles,
    }


def phase_gateway(ctx):
    import numpy as np

    from repro.fleet.plan import build_fleet_scenario
    from repro.fleet.stream import FleetRuntime, RuntimeConfig
    from repro.gateway import FleetGateway, GatewayConfig, TenantSpec

    n_tenants, n_links, hours = 64, 32, 30 * 24
    horizon = hours + CHUNK_K
    base = build_fleet_scenario(n_links, horizon=horizon, seed=SEED)
    scale = lambda i: base.demand * (1.0 + 0.01 * i)
    gw = FleetGateway(GatewayConfig(
        slots_per_bucket=n_tenants, queue_limit=n_tenants,
        obs=True, cadence=3 * CHUNK_K,
    ))
    for i in range(n_tenants):
        gw.join(f"t{i:04d}", TenantSpec(spec=base.fleet, demand=scale(i),
                                        config=RuntimeConfig(), horizon=horizon))
    check(gw.n_active == n_tenants, f"{gw.n_active}/{n_tenants} tenants active")
    probes = {0: [], n_tenants - 1: []}
    chunk_s = []
    for _ in range(hours // CHUNK_K):
        t0 = time.perf_counter()
        outs = gw.tick_many(CHUNK_K)
        chunk_s.append(time.perf_counter() - t0)
        for i, got in probes.items():
            got.append(outs[f"t{i:04d}"])
    check(gw.compiles == 2 * gw.n_buckets,
          f"{gw.compiles} compiles for {gw.n_buckets} buckets (want 2 each)")
    for i, got in probes.items():
        rt = FleetRuntime.from_config(base.fleet, RuntimeConfig())
        dem = scale(i)
        for t in range(hours):
            w = rt.step(np.ascontiguousarray(dem[:, t]))
            g = got[t // CHUNK_K]
            for f in STEP_FIELDS:
                check(np.array_equal(np.asarray(g[f])[:, t % CHUNK_K],
                                     np.asarray(w[f])),
                      f"tenant t{i:04d} field {f} hour {t} vs standalone runtime")
    violations = gw.check(final=True)
    check(not violations, f"gateway contract violations: {violations[:3]}")
    chunk_s = np.asarray(chunk_s)
    return {
        "tenants": n_tenants, "links_per_tenant": n_links, "hours": hours,
        "chunk_first_s": chunk_s[0], "chunk_steady_s": float(chunk_s[1:].mean()),
        "tenant_link_steps_per_s":
            n_tenants * n_links * CHUNK_K / float(chunk_s[1:].mean()),
        "compiles": gw.compiles, "buckets": gw.n_buckets,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--only", default=",".join(PHASES),
                    help="comma-separated subset of " + ",".join(PHASES))
    args = ap.parse_args()
    only = [p for p in args.only.split(",") if p]
    unknown = set(only) - set(PHASES)
    if unknown:
        ap.error(f"unknown phases {sorted(unknown)}")

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 2
    from benchmarks._util import use_compile_cache

    cache_dir = use_compile_cache()
    print(f"chip_smoke: {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}, compile cache {cache_dir}", flush=True)
    log = CompileLog()

    cache = {}

    def scenario():
        if "sc" not in cache:
            from repro.fleet.plan import build_fleet_scenario

            t0 = time.perf_counter()
            cache["sc"] = build_fleet_scenario(N_LINKS, horizon=HORIZON, seed=SEED)
            print(f"chip_smoke: fleet scenario {N_LINKS} x {HORIZON} built in "
                  f"{time.perf_counter() - t0:.1f}s (host)", flush=True)
        return cache["sc"]

    ctx = {"scenario": scenario}
    runners = {
        "kernels": lambda: phase_kernels(ctx),
        "offline": lambda: phase_offline(ctx),
        "topology": lambda: phase_topology(ctx),
        "stream": lambda: phase_stream(ctx, log),
        "gateway": lambda: phase_gateway(ctx),
    }
    failed = []
    for name in [p for p in PHASES if p in only]:
        c0, n0, h0 = log.snapshot()
        t0 = time.perf_counter()
        try:
            res, status = runners[name](), "PASS"
        except Exception:
            traceback.print_exc()
            res, status = {}, "FAIL"
            failed.append(name)
        c1, n1, h1 = log.snapshot()
        line = {"phase": name, "status": status,
                "wall_s": time.perf_counter() - t0, "compile_s": c1 - c0,
                "compiles": n1 - n0, "cache_hits": h1 - h0,
                "peak_bytes_in_use": _peak_bytes(), **res}
        print("chip_smoke: " + json.dumps(line, default=float), flush=True)
    if failed:
        print(f"chip_smoke: FAILED phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
