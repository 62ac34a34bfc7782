"""Observability layer tests: ring exactness, tracing, monitors, profiling.

The load-bearing pieces, each against an independent reference:

* the device :class:`MetricsRing` (cumulative compare-reduce binning, one
  packed drain vector) vs a direct numpy re-implementation, fleet AND
  topology routing, including the ``prev_state`` carry across drains;
* the trace recorder's lease lifecycle slices vs hand-built state sequences,
  and streamed-vs-offline trace equivalence (``trace_from_plan``);
* EVERY contract monitor firing on an injected fault — billing
  reconciliation, streamed-vs-offline divergence, regret, forecast
  calibration — and staying quiet on clean streams;
* the end-to-end drained aggregates of a real streamed run vs quantities
  recomputed from the run's own outputs.

(The obs-on/off decision bit-exactness property lives with the other
streaming contracts in ``tests/test_fleet_runtime.py``.)
"""
import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.togglecci import OFF, ON, WAITING
from repro.fleet.plan import (
    build_fleet_scenario,
    build_topology_scenario,
    forecast_gated_policy,
    optimize_routing,
)
from repro.fleet.stream import FleetRuntime
from repro.fleet.policy import fit_cost_coef
from repro.obs import profile
from repro.obs import (
    ContractViolation,
    DrainedMetrics,
    ObsConfig,
    TickProfiler,
    TraceRecorder,
    default_hist_edges,
    flatten_ring,
    init_ring,
    reset_ring,
    ring_layout,
    ring_size,
    trace_from_plan,
    update_ring,
)

STATES = (OFF, WAITING, ON)


# ---------------------------------------------------------------------------
# The device ring vs a numpy reference
# ---------------------------------------------------------------------------


def _numpy_ring_reference(ticks, edges, tier_bounds, routing_idx=None):
    """Re-derive every drained field from the raw per-tick inputs with plain
    numpy (searchsorted-style binning instead of compare-reductions)."""
    B = edges.shape[0] - 1
    K = tier_bounds.shape[1]
    out = {
        "requests": 0, "activations": 0, "releases": 0, "cci_gb": 0.0,
        "cost_hist": np.zeros(B), "tier_gb": np.zeros(K), "gauges": [],
    }
    prev = np.full(ticks[0]["state"].shape, OFF, np.int64)
    for tk in ticks:
        st, x = tk["state"], tk["x"]
        out["requests"] += int(np.sum((prev == OFF) & (st != OFF)))
        out["activations"] += int(np.sum((prev != ON) & (st == ON)))
        out["releases"] += int(np.sum((prev == ON) & (st == OFF)))
        prev = st
        on = x == 1
        on_pair = on[routing_idx] if routing_idx is not None else on
        out["cci_gb"] += float(np.sum(tk["d_pair"] * on_pair))
        vol = tk["d_pair"] * (1.0 - on_pair)
        idx = np.sum(
            tk["month_cum"][:, None] >= tier_bounds[:, : K - 1], axis=1
        )
        np.add.at(out["tier_gb"], idx, vol)
        realized = np.where(on, tk["cci"], tk["vpn"])
        bins = np.sum(realized[:, None] > edges[None, 1:B], axis=1)
        out["cost_hist"] += np.bincount(bins, minlength=B)
        pred = tk.get("pred")
        err = 0.0 if pred is None else float(np.abs(pred - tk["d_row"]).sum())
        out["gauges"].append([
            float(on.sum()), float(realized.sum()), float(tk["vpn"].sum()),
            float(tk["cci"].sum()), float(tk["d_pair"].sum()), err,
            0.0 if pred is None else float(pred.sum()),
            float(tk["d_row"].sum()),
        ])
    return out


def _random_tick(rng, M, P, pred=False):
    st = rng.choice(STATES, size=M)
    return {
        "state": st,
        "x": (st == ON).astype(np.int64),
        "vpn": rng.uniform(0.0, 500.0, M),
        "cci": rng.uniform(0.0, 500.0, M),
        "d_pair": rng.uniform(0.0, 300.0, P),
        "d_row": rng.uniform(0.0, 300.0, M),
        "month_cum": rng.uniform(0.0, 3000.0, P),
        "pred": rng.uniform(0.0, 300.0, M) if pred else None,
    }


@pytest.mark.parametrize("topology,pred", [(False, False), (True, True)])
def test_ring_matches_numpy_reference(topology, pred):
    rng = np.random.default_rng(3)
    M, cap, B, K = 5, 4, 6, 3
    P = 7 if topology else M
    routing_idx = rng.integers(0, M, P) if topology else None
    edges = default_hist_edges(B, 1e-1, 1e3)
    bounds = np.sort(rng.uniform(100, 2500, (P, K)), axis=1)
    bounds[:, -1] = np.inf
    ticks = [_random_tick(rng, M, P, pred) for _ in range(cap)]
    # Pin the tie semantics: a value exactly ON an edge stays in the lower
    # bin (strict > against the upper edge — left-searchsorted binning).
    ticks[0]["vpn"][0] = edges[2]
    ticks[0]["x"][0] = 0

    with jax.enable_x64():
        ring = init_ring(M, cap, B, K)
        for tk in ticks:
            ring = update_ring(
                ring, jnp.asarray(edges),
                x_t=jnp.asarray(tk["x"]), state_t=jnp.asarray(tk["state"]),
                vpn_t=jnp.asarray(tk["vpn"]), cci_t=jnp.asarray(tk["cci"]),
                d_pair=jnp.asarray(tk["d_pair"]),
                d_row=jnp.asarray(tk["d_row"]),
                month_cum=jnp.asarray(tk["month_cum"]),
                tier_bounds=jnp.asarray(bounds),
                routing_idx=(
                    jnp.asarray(routing_idx, jnp.int32) if topology else None
                ),
                pred_t=jnp.asarray(tk["pred"]) if pred else None,
            )
        vec = np.asarray(flatten_ring(ring))

    assert vec.shape == (ring_size(cap, B, K),)
    dm = DrainedMetrics.from_flat(10, vec, cap=cap, n_bins=B, n_tiers=K)
    ref = _numpy_ring_reference(ticks, edges, bounds, routing_idx)
    assert dm.hour == 10 and dm.ticks == cap
    assert dm.requests == ref["requests"]
    assert dm.activations == ref["activations"]
    assert dm.releases == ref["releases"]
    assert dm.cci_gb == pytest.approx(ref["cci_gb"], rel=1e-12)
    np.testing.assert_array_equal(dm.cost_hist, ref["cost_hist"])
    np.testing.assert_allclose(dm.tier_gb, ref["tier_gb"], rtol=1e-12)
    g = np.asarray(ref["gauges"])  # (ticks, 8) in GAUGES order
    for j, name in enumerate([
        "lease_on", "realized_cost", "vpn_cost", "cci_cost", "billed_gb",
        "forecast_abs_err", "pred_total", "demand_total",
    ]):
        np.testing.assert_allclose(
            getattr(dm, name), g[:, j], rtol=1e-12, err_msg=name
        )
    # The volume split closes: vpn tier buckets + cci path == billed total.
    assert dm.tier_gb.sum() + dm.cci_gb == pytest.approx(
        dm.billed_gb.sum(), rel=1e-12
    )


def test_ring_reset_carries_prev_state_across_drains():
    """Lease edges spanning a drain boundary are counted exactly once: the
    reset zeroes every accumulator but keeps the previous tick's FSM state."""
    M, cap, B, K = 3, 2, 4, 2
    edges = default_hist_edges(B)
    bounds = np.tile([50.0, np.inf], (M, 1))
    z = np.zeros(M)

    def upd(ring, st):
        st = np.asarray(st)
        return update_ring(
            ring, jnp.asarray(edges),
            x_t=jnp.asarray((st == ON).astype(np.int64)),
            state_t=jnp.asarray(st),
            vpn_t=jnp.asarray(z), cci_t=jnp.asarray(z),
            d_pair=jnp.asarray(z), d_row=jnp.asarray(z),
            month_cum=jnp.asarray(z), tier_bounds=jnp.asarray(bounds),
        )

    def drain(ring, hour):
        return DrainedMetrics.from_flat(
            hour, np.asarray(flatten_ring(ring)), cap=cap, n_bins=B, n_tiers=K
        )

    with jax.enable_x64():
        ring = init_ring(M, cap, B, K)
        ring = upd(ring, [WAITING, OFF, OFF])   # row 0 requests
        ring = upd(ring, [WAITING, OFF, OFF])
        a = drain(ring, 2)
        ring = reset_ring(ring)
        ring = upd(ring, [ON, OFF, OFF])        # activation in window 2
        b = drain(ring, 3)
    assert (a.requests, a.activations, a.releases) == (1, 0, 0)
    # Without the carry the WAITING→ON edge would double as a request.
    assert (b.requests, b.activations, b.releases) == (0, 1, 0)
    assert a.ticks == 2 and b.ticks == 1


def test_ring_layout_roundtrip():
    layout = ring_layout(cap=3, n_bins=4, n_tiers=2)
    assert sum(n for _, n in layout) == ring_size(3, 4, 2)
    names = [n for n, _ in layout]
    assert names[0] == "ticks" and "cost_hist" in names and "tier_gb" in names


# ---------------------------------------------------------------------------
# Trace recorder
# ---------------------------------------------------------------------------


def test_trace_lease_lifecycle_and_exports(tmp_path):
    rec = TraceRecorder(2, hour_us=1000.0, kind="port")
    seq = [
        [OFF, OFF], [WAITING, OFF], [WAITING, ON], [ON, ON], [ON, OFF],
        [OFF, OFF],
    ]
    for h, st in enumerate(seq):
        rec.observe_states(h, np.asarray(st))
    rec.instant(3, "reroute", moved_pairs=1, pairs=2)
    rec.counter(4, "lease_on", {"rows": 1.0})

    toggles = [e for e in rec.events if e["type"] == "toggle"]
    assert [(e["row"], e["event"]) for e in toggles] == [
        (0, "request"),                   # h1: row0 OFF→WAITING
        (1, "request"), (1, "activate"),  # h2: row1 OFF→ON (D = 0 edge)
        (0, "activate"),                  # h3: row0 WAITING→ON
        (1, "release"),                   # h4
        (0, "release"),                   # h5
    ]
    ct = rec.chrome_trace()
    evs = ct["traceEvents"]
    assert [e["args"]["name"] for e in evs if e["ph"] == "M"] == [
        "port0", "port1"
    ]
    row0 = sorted(
        [e for e in evs if e["ph"] == "X" and e["tid"] == 0],
        key=lambda s: s["ts"],
    )
    # Row 0: provisioning h1→h3 (the D_cci delay edge), leased h3→h5.
    assert [s["name"] for s in row0] == ["provisioning", "leased"]
    assert row0[0]["ts"] == 1000.0 and row0[0]["dur"] == 2000.0
    assert row0[1]["ts"] == 3000.0 and row0[1]["dur"] == 2000.0
    assert any(e["ph"] == "i" and e["name"] == "reroute" for e in evs)
    assert any(e["ph"] == "C" and e["name"] == "lease_on" for e in evs)

    p = rec.save_chrome(str(tmp_path / "t.json"))
    with open(p) as f:
        assert json.load(f)["traceEvents"]
    pj = rec.save_jsonl(str(tmp_path / "t.jsonl"))
    with open(pj) as f:
        lines = [json.loads(line) for line in f]
    assert len(lines) == rec.n_events == 8  # 6 toggles + reroute + counter


def test_trace_open_lease_closed_at_horizon():
    rec = TraceRecorder(1)
    rec.observe_states(0, np.asarray([ON]))  # leased, never released
    slices = [e for e in rec.chrome_trace()["traceEvents"] if e["ph"] == "X"]
    assert [s["name"] for s in slices] == ["provisioning", "leased"]


def test_trace_from_plan_matches_streamed():
    """Offline plans and streamed runs must render identically: feeding the
    plan's state matrix column by column == trace_from_plan in one call."""
    rng = np.random.default_rng(0)
    states = rng.choice(STATES, size=(3, 40))
    a = trace_from_plan(states, kind="link")
    b = TraceRecorder(3, kind="link")
    for t in range(states.shape[1]):
        b.observe_states(t, states[:, t])
    assert a.events == b.events
    assert a.chrome_trace() == b.chrome_trace()


# ---------------------------------------------------------------------------
# Contract monitors: clean streams pass, injected faults fire
# ---------------------------------------------------------------------------


def _fleet_rt(obs, seed=0, n=6, horizon=220):
    sc = build_fleet_scenario(n, horizon=horizon, history_hours=100, seed=seed)
    return FleetRuntime(sc.fleet, obs=obs), sc


def test_clean_stream_all_monitors_pass():
    rt, sc = _fleet_rt(ObsConfig(cadence=32, divergence=True))
    rt.run(sc.demand)
    rt.obs_check(final=True)  # no violation on an honest stream
    rep = rt.obs_report()
    assert rep.violations == []
    assert rep.monitors["billing"]["checks"] > 0
    assert rep.monitors["divergence"]["checks"] > 0


def test_billing_monitor_fires_on_corrupted_accumulator():
    rt, sc = _fleet_rt(ObsConfig(cadence=32))
    rt.run(sc.demand)
    rt._state.vpn_pref[2] *= 1.01  # simulated accumulator corruption
    with pytest.raises(ContractViolation, match="billing") as ei:
        rt.obs_check()
    v = ei.value
    assert v.monitor == "billing" and v.row == 2
    assert v.details["accumulator"] == "vpn_pref"
    assert str(v) in [str(x) for x in rt.obs.violations]  # recorded too


def test_billing_monitor_fires_on_drained_total_mismatch():
    rt, sc = _fleet_rt(ObsConfig(cadence=32))
    rt.run(sc.demand)
    rt.obs.billing.dev["realized"] *= 1.5  # device totals vs host sums
    with pytest.raises(ContractViolation, match="realized"):
        rt.obs_check()


def test_divergence_monitor_fires_on_flipped_decision():
    rt, sc = _fleet_rt(ObsConfig(cadence=64, divergence=True))
    rt.run(sc.demand)
    mon = rt.obs.divergence
    mon.x[40] = 1 - mon.x[40]  # one observed decision column corrupted
    with pytest.raises(ContractViolation, match="diverged") as ei:
        rt.obs_check()
    assert ei.value.monitor == "divergence" and ei.value.hour == 40


def test_divergence_monitor_covers_mid_stream_reroute():
    """Topology mode: the recorded routing SCHEDULE feeds the offline replay,
    so a clean stream with a mid-stream reroute still reconciles."""
    sc = build_topology_scenario(8, n_facilities=3, horizon=200, seed=1)
    r0 = optimize_routing(sc.topo, sc.demand)
    rt = FleetRuntime(
        sc.topo, routing=r0, obs=ObsConfig(cadence=32, divergence=True)
    )
    idx = np.asarray(r0.primary).copy()
    for i, pr in enumerate(sc.topo.pairs):
        others = [c for c in pr.candidates if c != idx[i]]
        if others:
            idx[i] = int(others[0])
            break
    moved = not np.array_equal(idx, np.asarray(r0.primary))
    r1 = sc.topo.plan(idx)
    for t in range(sc.demand.shape[1]):
        if t == 100 and moved:
            rt.reroute(r1)
        rt.step(sc.demand[:, t])
    rt.obs_check(final=True)
    s = rt.obs.divergence.summary()
    assert s["checks"] == 1
    assert s["routing_segments"] == (2 if moved else 1)


def test_divergence_monitor_disables_with_reason_on_endo():
    rt, sc = _fleet_rt(ObsConfig(cadence=32, divergence=True))
    rt.step(sc.demand[:, 0], cci_demand_t=sc.demand[:, 0] * 0.25)
    s = rt.obs.divergence.summary()
    assert s["enabled"] is False and "endogenous" in s["reason"]
    rt.obs_check()  # disabled monitor never raises


def test_regret_monitor_fires_on_injected_overrun():
    rt, sc = _fleet_rt(ObsConfig(cadence=32, max_regret_vs_static=1.0))
    rt.run(sc.demand)
    rt.obs_check(final=True)  # honest run stays within 100% of best-static
    rt.obs.regret.realized *= 3.0  # injected cost-accounting fault
    with pytest.raises(ContractViolation, match="best-static") as ei:
        rt.obs_check(final=True)
    assert ei.value.monitor == "regret"
    assert ei.value.details["regret_vs_static"] > 1.0


def test_regret_monitor_oracle_ratio_fires():
    rt, sc = _fleet_rt(
        ObsConfig(cadence=64, max_oracle_ratio=2.0), n=2, horizon=150
    )
    rt.run(sc.demand)
    rt.obs_check(final=True)
    assert rt.obs.regret.oracle_ratio is not None
    assert rt.obs.regret.oracle_ratio >= 0.999  # the DP is a true lower bound
    rt.obs.regret.realized *= 3.0
    with pytest.raises(ContractViolation, match="oracle"):
        rt.obs_check(final=True)


def test_calibration_monitor_fires_on_biased_forecast():
    rt, sc = _fleet_rt(None)  # prime a reactive pass for the coefficients
    base = rt.run(sc.demand)
    with jax.enable_x64():
        arrays = sc.fleet.stack(jnp.float64)
        coef = np.asarray(fit_cost_coef(
            jnp.asarray(sc.demand), jnp.asarray(base["vpn_cost"]),
            jnp.asarray(base["cci_cost"]),
        ))
        pol = forecast_gated_policy(
            arrays.toggle, sc.demand * 3.0, margin=0.05, cost_coef=coef
        )
    ort = FleetRuntime(
        arrays, policy=pol, hours_per_month=sc.fleet.hours_per_month,
        obs=ObsConfig(cadence=32, max_forecast_bias=1.5),
    )
    with pytest.raises(ContractViolation, match="bias") as ei:
        ort.run(sc.demand)  # fires mid-stream, inside step()
    assert ei.value.monitor == "calibration"
    assert ort.t == 32  # caught at the FIRST drain, not end of run
    assert ei.value.details["bias"] > 1.5


def test_calibration_inactive_for_memoryless_policies():
    rt, sc = _fleet_rt(ObsConfig(cadence=32, max_forecast_bias=1.01))
    rt.run(sc.demand[:, :40])
    rt.obs_check()  # inactive (reactive policy) — never raises
    s = rt.obs.calibration.summary()
    assert s["enabled"] is False and "forecast" in s["reason"]


# ---------------------------------------------------------------------------
# End-to-end drained aggregates + report + profiler
# ---------------------------------------------------------------------------


def test_streamed_report_aggregates_match_outputs():
    T = 220
    rt, sc = _fleet_rt(ObsConfig(cadence=64), horizon=T)
    out = rt.run(sc.demand)
    rep = rt.obs_report()

    # Lease lifecycle counts recomputed from the emitted state matrix.
    st = np.concatenate(
        [np.full((rt.n_rows, 1), OFF), out["state"]], axis=1
    )
    prev, cur = st[:, :-1], st[:, 1:]
    assert rep.requests == int(np.sum((prev == OFF) & (cur != OFF)))
    assert rep.activations == int(np.sum((prev != ON) & (cur == ON)))
    assert rep.releases == int(np.sum((prev == ON) & (cur == OFF)))
    assert rep.hours == T
    assert rep.drains == 4  # 3 device drains + the report's partial flush
    assert rep.realized_cost == pytest.approx(out["cost"].sum(), rel=1e-9)
    assert rep.vpn_cost == pytest.approx(out["vpn_cost"].sum(), rel=1e-9)
    d_clip = np.minimum(sc.demand, np.asarray(rt.arrays.capacity)[:, None])
    assert rep.billed_gb == pytest.approx(d_clip.sum(), rel=1e-9)
    assert sum(rep.vpn_tier_gb) + rep.cci_path_gb == pytest.approx(
        rep.billed_gb, rel=1e-9
    )
    assert rep.lease_on_mean == pytest.approx(np.mean(out["x"].sum(axis=0)))

    # run() steps one hour per call: one fleet.step span per hour.
    p = rep.profile
    assert p["calls"] == T and p["drains"] == 4
    assert p["h2d_bytes"] > 0 and p["d2h_bytes"] > 0
    assert p["call_us_p50"] <= p["call_us_p95"] <= p["call_us_p99"]
    for q in ("p50", "p95", "p99"):
        assert np.isfinite(rep.cost_quantiles[q])

    txt = rep.render_text()
    assert "observability report" in txt and "violations: none" in txt
    parsed = json.loads(rep.to_json())
    assert parsed["hours"] == T and parsed["trace_events"] == rep.trace_events
    assert rep.trace_events > 0

    # reset() starts a fresh observation run (fresh monitors and profile).
    rt.reset()
    assert rt.obs.profiler.n_calls == 0 and rt.obs.drained == []


def test_profiler_unit():
    """The profiler reads each call's ``fleet.step`` span and the byte
    counters noted inside it from the recorder; a call it has read once,
    or one the recorder did not keep, adds nothing."""
    tp = TickProfiler()
    assert np.isnan(tp.percentiles()["p50"])
    t0 = time.perf_counter()
    profile.force_recording(True)
    try:
        for _ in range(3):
            with profile.span("fleet.step"):
                profile.count("fleet.step.h2d_bytes", 100)
                profile.count("fleet.step.d2h_bytes", 200)
            tp.record_call()
            tp.record_call()
        profile.count("fleet.step.h2d_bytes", 7)   # outside every call
        profile.force_recording(False)
        with profile.span("fleet.step"):
            pass
        tp.record_call()
    finally:
        profile.force_recording(None)
    tp.note_drain()
    spans, _ = profile.recorded(t0)
    dur_us = [(e - b) * 1e6 for name, b, e in spans if name == "fleet.step"]
    s = tp.summary()
    assert s["calls"] == 3 == len(dur_us) and s["drains"] == 1
    assert s["h2d_bytes"] == 300 and s["d2h_bytes"] == 600
    assert s["call_us_p50"] == pytest.approx(float(np.median(dur_us)))


def test_obs_requires_flag():
    rt, _ = _fleet_rt(None)
    assert rt.obs is None
    with pytest.raises(AssertionError, match="obs="):
        rt.obs_report()
    with pytest.raises(AssertionError, match="obs="):
        rt.obs_check()
