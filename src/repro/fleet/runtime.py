"""Streaming fleet runtime: the online half of the planning stack.

Everything before this module is *offline*: ``plan_fleet`` / ``plan_topology``
consume the whole 8760-hour demand matrix in one call. The paper's ToggleCCI
is an *online* algorithm, though — and a serving system only ever sees one
hour at a time. :class:`FleetRuntime` steps the SAME pluggable policy layer
(:mod:`repro.fleet.policy`) one tick at a time over every link/port in ONE
jitted vmapped step, carrying all policy state explicitly:

* the FSM carry (state / dwell counters — whatever ``policy.init_carry``
  returns, vmapped per row);
* the sliding-window state — NOT a naive running sum: the offline kernel
  computes ``r[t] = pref[t] − pref[max(0, t−h)]`` from float64 prefix sums,
  so the runtime carries the running prefix and a ring buffer of past prefix
  VALUES and takes the same difference. Add/subtract ring buffers drift from
  prefix differences in floating point; prefix rings make N incremental
  steps decision-BIT-EXACT with one offline ``policy_scan``
  (property-tested in ``tests/test_fleet_runtime.py``);
* the billing state (cumulative volume + value at month start, so the
  tiered VPN rate matches :func:`repro.core.costmodel.monthly_cumsum`
  exactly);
* the forecast SSM state (:func:`repro.models.ssm.demand_forecaster_step`)
  when the policy is forecast-gated and runs in live mode.

Two demand routings, mirroring the offline engines: *fleet* (each row one
link) and *topology* (pair demand folded onto shared CCI ports through the
routing legs, pair-level tier state + port-level FSMs). In topology mode
the routing — a :class:`repro.fleet.routing.RoutingPlan`, stacked to its
padded leg-list operand — is part of :class:`RuntimeState`, a swappable
traceable operand of the compiled tick: multi-hop relay paths and multicast
forwarding trees are just extra weighted legs under the same ``segment_sum``,
and :meth:`FleetRuntime.reroute` swaps any plan fitting the compiled leg
bound MID-STREAM without recompiling or touching any carried state: from the
swap tick on, decisions are bit-exact vs an offline
:func:`repro.fleet.engine.replay_plan_topology` that applies the same
routing at the same hour (property-tested in ``tests/test_fleet_runtime.py``).

On top sits the actuation layer (ROADMAP "elastic serving integration"):
:class:`ElasticFleetPlanner` is the N-link generalization of
:class:`repro.core.planner.InterconnectPlanner` — per-link modes select the
hierarchical full-precision vs int8-compressed ``sync_grads`` path
(:mod:`repro.dist.collectives`), and the compressed path's ~4x billed-GB
reduction feeds back as next-hour demand: the endogenous loop CCI-style
studies treat as exogenous.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, NamedTuple, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.costmodel import tier_segment
from repro.core.planner import COMPRESS_RATIO, collective_mode
from repro.obs.metrics import flatten_ring, init_ring, reset_ring, update_ring
from repro.obs.profile import count, span

from .policy import ForecastGatedPolicy, make_policy, predicted_mode_costs
from .routing import RoutingOperand, RoutingPlan, as_routing_plan
from .spec import FleetArrays, FleetSpec
from .topology import TopologyArrays, TopologySpec

_STEP_CACHE: dict = {}


class RuntimeState(NamedTuple):
    """The explicit carry of one streaming step.

    Split by residence: the FSM carry and the forecaster's SSM state are
    device-side (donated through the jitted step). The float64 cost/demand
    PREFIX accumulators are ADDED on the device, in the same sequential
    order as the offline engine's ``prefix_sum``, and mirrored here in
    numpy; the prefix ring buffers live host-side only (an in-jit ring
    defeats XLA's donation aliasing: the read forces a copy-on-write of the
    whole ring every step). Every add happening on the device keeps streams
    bit-exact with offline plans on a TPU too, whose float64 is a pair of
    float32s and so differs from numpy's in the low bits.

    Demand/billing rows are per PAIR (== per link in fleet mode); cost
    prefix rows are per PORT (== per link in fleet mode).
    """

    t: int                  # the tick about to be served
    fsm: tuple              # device: policy carry, leaves (rows,)
    ssm_h: jax.Array        # device: (M, S) live forecaster state ((M, 0) unused)
    t_dev: jax.Array        # device twin of t (transfers cost ~100µs; the
                            # replay index must not pay one per tick)
    routing: object         # device: RoutingOperand leg list in topology
                            # mode (None in fleet mode) — the padded
                            # (row, port, weight) legs the tick aggregates
                            # with (segment_sum over leg_port, matching the
                            # offline engine bit-for-bit) plus the (P,)
                            # primary first-hop twin the obs ring and
                            # modes() consume; swappable mid-stream via
                            # FleetRuntime.reroute() at a fixed leg bound
    dcum: np.ndarray        # (P,) cumulative clipped billed demand, == full[t]
    month_vol: np.ndarray   # (P,) clipped billed demand so far this month
    vpn_pref: np.ndarray    # (M,) exclusive prefix of hourly VPN cost
    cci_pref: np.ndarray    # (M,) exclusive prefix of hourly CCI cost
    ring_vpn: np.ndarray    # (Hbuf, M) past vpn_pref values, slot = hour % Hbuf
                            # — hour-MAJOR so chunk commits are
                            # contiguous memcpys
    ring_cci: np.ndarray    # (Hbuf, M)
    pred_live: np.ndarray   # (M,) next-tick demand forecast (zeros when unused)
    metrics: object         # device: obs MetricsRing pytree (None when the
                            # runtime was built without observability) —
                            # updated inside the jitted tick, drained onto
                            # the packed D2H transfer at the obs cadence


@dataclasses.dataclass(frozen=True)
class StreamingForecaster:
    """A trained demand forecaster packaged for O(1)-per-tick stepping.

    ``fit`` trains the :mod:`repro.models.ssm` head on a strictly-earlier
    history block and warms the recurrent state through it, so live
    predictions are causal from tick 0 — ``pred0`` is the readout after the
    last history hour, exactly ``forecast_port_demand``'s first live column.
    """

    params: dict            # demand-forecaster readout/EMA parameters
    scale: np.ndarray       # (rows,) per-row mean normalizers
    h0: np.ndarray          # (rows, S) state after consuming the history
    pred0: np.ndarray       # (rows,) forecast for live hour 0, GB/hr

    @classmethod
    def fit(cls, history, window: int, **train_kw) -> "StreamingForecaster":
        from repro.models.ssm import (
            demand_forecaster_apply,
            demand_forecaster_state,
            train_demand_forecaster,
        )

        history = np.asarray(history, np.float64)
        assert history.ndim == 2 and history.shape[1] >= 2, (
            "StreamingForecaster.fit needs a (rows, H>=2) history block — "
            "live streaming has no future to fit on"
        )
        params, scale = train_demand_forecaster(history, window, **train_kw)
        u = jnp.log1p(jnp.asarray(history / scale[:, None], jnp.float32))
        y = np.asarray(demand_forecaster_apply(params, u), np.float64)
        pred0 = np.maximum(np.expm1(y[:, -1]), 0.0) * scale
        h0 = np.asarray(demand_forecaster_state(params, u))
        return cls(params=params, scale=scale, h0=h0, pred0=pred0)


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Frozen construction options of a :class:`FleetRuntime`.

    The one validated bundle behind BOTH construction surfaces: the classic
    keyword pile (``FleetRuntime(spec, policy=..., obs=...)`` — still
    supported; it builds a config internally) and the explicit
    :meth:`FleetRuntime.from_config`. The multi-tenant gateway embeds the
    same object in its ``TenantSpec``, so standalone and pooled runtimes
    share one validation path and one source of construction truth.

    Fields mirror the runtime keywords exactly; see
    :class:`FleetRuntime` for their semantics. ``hours_per_month`` is
    overridden by the spec's calendar when a spec (not pre-stacked arrays)
    is given, same as the keyword always was.
    """

    routing: object = None
    policy: object = None
    hours_per_month: int = 730
    renew_in_chunks: bool = False
    forecaster: Optional[StreamingForecaster] = None
    obs: object = None

    def validate(self) -> "RuntimeConfig":
        if not (int(self.hours_per_month) >= 1):
            raise ValueError(
                f"hours_per_month must be >= 1, got {self.hours_per_month}"
            )
        if self.forecaster is not None:
            if not isinstance(self.forecaster, StreamingForecaster):
                raise TypeError(
                    "forecaster must be a StreamingForecaster, got "
                    f"{type(self.forecaster).__name__}"
                )
            if self.policy is not None and not isinstance(
                self.policy, ForecastGatedPolicy
            ):
                raise ValueError(
                    "forecaster= only applies to a ForecastGatedPolicy"
                )
        if self.obs not in (None, True, False) and not hasattr(
            self.obs, "cadence"
        ):
            raise TypeError(
                "obs must be None, a bool, or an ObsConfig-like object "
                f"with a drain cadence — got {type(self.obs).__name__}"
            )
        return self


def _build_step_many(
    topology: bool, pred_source: Optional[str], endo: bool,
    obs: bool = False, drain: bool = False, K: int = 1,
):
    """K hours in ONE dispatch: batched pricing planes + an FSM-only scan.

    The decomposition that makes chunking a real amortization (and not just
    K per-tick bodies inside a loop): everything that depends ONLY on the
    demand block — capacity clipping, tiered transfer pricing, route
    aggregation, forecast-gate features — is computed as ``(rows, K)``
    PLANE ops before the scan, exactly the offline engines' formulation
    (whose bit-parity with per-tick stepping is the PR-5 contract: every
    op is elementwise per (row, hour), so batching reassociates nothing).
    What remains sequential is genuinely sequential state:

    * the billing calendar (``dcum`` and the month-to-date volume, reset
      at month boundaries) — a tiny ``lax.scan`` over (P,) adds, the same
      adds in the same order as the offline ``monthly_cumsum``;
    * the toggle window prefixes — same tiny scan shape, emitting the
      start-of-hour snapshots the window sums and ring writes need;
    * the FSM transition itself (+ the SSM forecaster step and metrics
      ring update in live/obs modes) — the ONLY per-row work left in the
      main scan body.

    The (M, hbuf) prefix window rings never touch the device AT ALL: any
    formulation that keeps them in the jitted fn pays ring-sized memory
    traffic per chunk (a carried dynamic-update-slice copies the whole
    ring every inner step, ~26 ms/chunk at 2048x337 f64; a hoisted
    scatter ~4 ms/chunk on CPU). The caller gathers the pre-chunk window
    reads from its numpy ring twins (:func:`_ring_reads`) into the
    chunk's single H2D block; in-chunk reads (rows with window h < K)
    come from the prefix-scan snapshot planes instead. Same f64 values
    either way: host and device prefixes are bit-identical twins.

    ``hpm`` (the billing calendar) rides as a traced int operand so
    calendars don't multiply compiled variants; ``K`` is static (one
    compiled chunk per length). ``drain``: with obs on, the metrics ring
    is flattened/reset AFTER the scan — equivalent to the per-tick drain
    variant firing on the chunk's last hour, which is the only hour a
    drain cadence boundary is allowed to touch (the caller asserts the
    alignment). Per-hour outputs are ``(K, rows)`` planes in
    :func:`_chunk_planes` order, from which the host builds each hour's
    ``step()`` dict and mirrors the accumulators; :func:`_pack_chunk` packs
    them for the trip home. Chunkings are property-tested bit-exact against
    each other in ``tests/test_fleet_runtime.py``.
    """

    def step_many(arrays, policy, fc, fsm, ssm_h, t, routing, ring,
                  hist_edges, hpm, seq, demand_block):
        f = jnp.result_type(float)
        P = (arrays.pair_capacity if topology else arrays.capacity).shape[0]
        M = arrays.toggle.theta1.shape[0]
        dcum, month_vol, vpn_pref, cci_pref, pred_live = seq
        h = jnp.broadcast_to(jnp.asarray(arrays.toggle.h, jnp.int32), (M,))
        t0 = t
        ks = jnp.arange(K, dtype=jnp.result_type(t))

        # --- unpack the single packed H2D block ---------------------------
        # FLAT 1D layout, every segment written contiguously on the host:
        # K*P demand values in the caller's native (P, K) row-major order
        # [+ K*P endo], then the host's pre-chunk window-ring reads as
        # (K, M) planes (prefix values at hour t+k-h for slots older than
        # the chunk — gathered from the numpy ring twins straight into the
        # buffer). The demand transpose to (K, P) happens HERE, on device,
        # where it fuses into the pricing clamp; every plane after it keeps
        # the hours-leading, rows-minor orientation, so the scans consume
        # rows directly and the output planes ship home transpose-free.
        nd = (2 if endo else 1) * K * P
        d_cols = demand_block[:K * P].reshape(P, K).T         # (K, P)
        pre_v = demand_block[nd:nd + K * M].reshape(K, M)
        pre_c = demand_block[nd + K * M:nd + 2 * K * M].reshape(K, M)

        # --- pricing planes (demand-only; the offline formulation) --------
        with jax.named_scope("pricing"):
            cap = arrays.pair_capacity if topology else arrays.capacity
            d_pair = jnp.minimum(d_cols.astype(f), cap[None, :])  # (K, P)
            if endo:
                d_cci_raw = jnp.minimum(
                    demand_block[K * P:2 * K * P].reshape(P, K).T.astype(f),
                    cap[None, :],
                )
            else:
                d_cci_raw = d_pair

        # Billing calendar: sequential month-boundary resets over (P,)
        # vectors (one f64 add + one select per hour, as prefix_sum adds; a
        # parallel cumsum would reassociate, this does not).
        with jax.named_scope("calendar_scan"):
            def cal_body(carry, d_k):
                dcum, mv, tk = carry
                mv = jnp.where(tk % hpm == 0, jnp.zeros_like(mv), mv)
                return (dcum + d_k, mv + d_k, tk + 1), mv

            (dcum, month_vol, _), month_cum = jax.lax.scan(
                cal_body, (dcum, month_vol, t0), d_pair
            )                                                     # (K, P)

        # Tier pricing, unrolled over the Kt tier columns so every
        # intermediate is a fusible (K, P) plane. This is the same
        # per-element f64 op chain as tiered_marginal_cost_tables —
        # tier_segment per tier and a left fold from zero over tiers —
        # so the bits match the per-tick path exactly; the broadcast
        # (K, P, Kt) temps of the table formulation stay unfused on
        # XLA:CPU and cost ~15MB of memory traffic per chunk.
        with jax.named_scope("pricing"):
            bounds = arrays.tier_bounds.astype(f)                 # (P, Kt)
            rates = arrays.tier_rates.astype(f)
            vpn_transfer = jnp.zeros((), f)
            prev_b = jnp.zeros((bounds.shape[0],), f)
            for j in range(bounds.shape[-1]):
                seg_j = tier_segment(
                    month_cum, d_pair, prev_b[None, :], bounds[None, :, j]
                )
                # Same FMA guard as tiered_marginal_cost_tables: the where()
                # keeps LLVM from contracting the product into the fold add
                # (contraction is per-fusion-context, so chunked bits would
                # drift from per-tick bits).
                vpn_transfer = vpn_transfer + jnp.where(
                    seg_j > 0, seg_j * rates[None, :, j], 0.0
                )
                prev_b = bounds[:, j]
            if topology:
                vpn_pair = arrays.L_vpn[None, :] + vpn_transfer   # (K, P)
                # Same leg-list aggregation as the per-tick step, vmapped
                # over the chunk's K hour planes (each hour is the identical
                # per-element gather/weight/segment chain — bit parity
                # holds).
                lp, lm = routing.leg_pair, routing.leg_port
                vw, aw = routing.vpn_w, routing.attach_w
                seg = jax.vmap(
                    lambda v: jax.ops.segment_sum(v, lm, num_segments=M)
                )
                vpn_t = seg(vpn_pair[:, lp] * vw[None, :])        # (K, M)
                d_bill = jnp.minimum(
                    seg(d_cci_raw[:, lp] * aw[None, :]),
                    arrays.port_capacity[None, :],
                )
                n_pairs = jax.ops.segment_sum(aw, lm, num_segments=M)  # (M,)
                cci_t = (
                    arrays.L_cci[None, :]
                    + arrays.V_cci[None, :] * n_pairs[None, :]
                    + arrays.c_cci[None, :] * d_bill
                )
                d_row = jnp.minimum(
                    seg(d_pair[:, lp] * aw[None, :]),
                    arrays.port_capacity[None, :],
                )
            else:
                vpn_t = arrays.L_vpn[None, :] + vpn_transfer
                cci_t = (
                    (arrays.L_cci + arrays.V_cci)[None, :]
                    + arrays.c_cci[None, :] * d_cci_raw
                )
                d_row = d_pair

        # --- toggle window planes -----------------------------------------
        # Start-of-hour prefix snapshots (the exclusive-prefix convention:
        # snapshot BEFORE the hour's cost is absorbed), then window sums
        # against the hoisted ring reads.
        with jax.named_scope("prefix_scan"):
            def pref_body(carry, vc):
                vpn_pref, cci_pref = carry
                v_k, c_k = vc
                return (vpn_pref + v_k, cci_pref + c_k), (vpn_pref, cci_pref)

            (vpn_pref, cci_pref), (snap_v, snap_c) = jax.lax.scan(
                pref_body, (vpn_pref, cci_pref), (vpn_t, cci_t)
            )                                                 # snaps (K, M)
        with jax.named_scope("window_sums"):
            lo = jnp.maximum(0, (t0 + ks)[:, None] - h[None, :])  # (K, M)
            in_chunk = lo >= t0
            jj = jnp.clip(lo - t0, 0, K - 1)
            in_v = jnp.take_along_axis(snap_v, jj, axis=0)
            in_c = jnp.take_along_axis(snap_c, jj, axis=0)
            r_vpn = snap_v - jnp.where(in_chunk, in_v, pre_v)     # (K, M)
            r_cci = snap_c - jnp.where(in_chunk, in_c, pre_c)

        # --- forecast gate features ---------------------------------------
        pred_cols = None                                      # (K, M)
        if pred_source == "replay":
            idx = jnp.clip(t0 + ks, 0, policy.pred_demand.shape[1] - 1)
            pred_cols = jnp.take(policy.pred_demand, idx, axis=1).T
            extras_cols = predicted_mode_costs(
                pred_cols, policy.cost_coef, f
            )                                                 # ((K, M) x2)

        # --- the sequential core: FSM (+ SSM / metrics ring) --------------
        # xs is a dict pytree of per-hour columns; only what THIS variant's
        # body consumes rides in it, so the scan carry stays minimal (the
        # FSM state, the small metrics ring, the SSM hidden state).
        xs = {"r_vpn": r_vpn, "r_cci": r_cci}
        if pred_source == "replay":
            xs["extras_v"], xs["extras_c"] = extras_cols
            if obs:
                xs["pred_t"] = pred_cols
        if pred_source == "live":
            xs["d_row"] = d_row
        if obs:
            xs.update(vpn_t=vpn_t, cci_t=cci_t, d_pair=d_pair,
                      d_row_obs=d_row, month_cum=month_cum)

        def body(carry, x):
            fsm, ssm_h, ring, pred_live = carry
            pred_t = None
            if pred_source is None:
                extras = None
            elif pred_source == "replay":
                extras = (x["extras_v"], x["extras_c"])
                pred_t = x.get("pred_t")
            else:
                pred_t = pred_live
                extras = predicted_mode_costs(pred_t, policy.cost_coef, f)
            fsm, (x_t, state_t) = jax.vmap(
                lambda p, c, w, e: p.step(c, w, e)
            )(policy, fsm, (x["r_vpn"], x["r_cci"]), extras)
            ys_t = (x_t.astype(f), state_t.astype(f))
            if pred_source == "live":
                from repro.models.ssm import demand_forecaster_step

                u_t = jnp.log1p((x["d_row"] / fc["scale"]).astype(jnp.float32))
                ssm_h, y_t = demand_forecaster_step(fc["params"], ssm_h, u_t)
                pred_live = (
                    jnp.maximum(jnp.expm1(y_t.astype(f)), 0.0) * fc["scale"]
                )
                ys_t = ys_t + (pred_live,)
            if obs:
                ring = update_ring(
                    ring, hist_edges,
                    x_t=x_t, state_t=state_t, vpn_t=x["vpn_t"],
                    cci_t=x["cci_t"], d_pair=x["d_pair"],
                    d_row=x["d_row_obs"], month_cum=x["month_cum"],
                    tier_bounds=arrays.tier_bounds,
                    routing_idx=routing.primary if topology else None,
                    pred_t=pred_t,
                )
            return (fsm, ssm_h, ring, pred_live), ys_t

        with jax.named_scope("fsm_scan"):
            (fsm, ssm_h, ring, pred_live), ys_t = jax.lax.scan(
                body, (fsm, ssm_h, ring, pred_live), xs, length=K
            )

        # --- commit + assemble --------------------------------------------
        # Ring writes are the HOST's job (its replay loop updates the numpy
        # ring twins); the device carry is the small vectors only.
        seq_out = (dcum, month_vol, vpn_pref, cci_pref, pred_live)
        # Per-hour outputs as separate (K, rows) planes in _chunk_planes
        # order. Packing them for the trip home is the caller's job
        # (_pack_chunk). The prefix snapshots ride home too: they ARE the
        # host replay (snap[k] = prefix before hour t+k, the ring-write
        # values), so the host adopts them instead of re-accumulating K
        # columns itself.
        named = dict(x=ys_t[0], state=ys_t[1], vpn_t=vpn_t, cci_t=cci_t,
                     d_pair=d_pair, r_vpn=r_vpn, r_cci=r_cci,
                     snap_v=snap_v, snap_c=snap_c)
        if pred_source == "live":
            named["pred"] = ys_t[2]
        planes = tuple(named[n] for n in _chunk_planes(pred_source))
        drain_vec = None
        if obs and drain:
            drain_vec = flatten_ring(ring)
            ring = reset_ring(ring)
        return fsm, ssm_h, t0 + K, ring, seq_out, planes, drain_vec

    return step_many


def _chunk_planes(pred_source: Optional[str]) -> Tuple[str, ...]:
    """The names of :func:`_build_step_many`'s ``planes``, in its order."""
    return ("x", "state", "vpn_t", "cci_t", "d_pair") + (
        ("pred",) if pred_source == "live" else ()
    ) + ("r_vpn", "r_cci", "snap_v", "snap_c")


def _packed_planes(pred_source: Optional[str], d_pair: bool) -> Tuple[str, ...]:
    """The float64 planes a stream's ``vals`` carries, in order: the ones
    the host reads on every call, then the live forecast (the host adopts
    its last hour) and ``d_pair`` (the observer's, and the gateway's
    billing)."""
    names = ("vpn_t", "cci_t", "r_vpn", "r_cci", "snap_v", "snap_c")
    if pred_source == "live":
        names += ("pred",)
    if d_pair:
        names += ("d_pair",)
    return names


def _pack_chunk(planes: dict, seq, names: Sequence[str]):
    """A chunk's outputs packed for ONE trip home, on the device: on the
    chip each device array is its own blocking D2H copy, whose fixed cost
    is well above that of its bytes. Two buffers, from ``planes`` (a dict by
    :func:`_chunk_planes` name; the gateway vmaps over its slot axis):

    * ``dec``: int8 ``(2, K, M)``, the ``x`` and FSM ``state`` planes (0/1
      and OFF/WAITING/ON, exact in int8; the builder emits them as float64);
    * ``vals``: one flat float64 vector, the ``names`` planes in order
      (``(K, M)`` each, ``d_pair`` ``(K, P)``), then the accumulators
      ``dcum``, ``month_vol`` (``(P,)``), ``vpn_pref``, ``cci_pref`` (``(M,)``).

    Both are exact, so every value is the builder's bits;
    :func:`_unpack_chunk` is the host-side inverse.
    """
    dec = jnp.stack([planes["x"], planes["state"]]).astype(jnp.int8)
    vals = jnp.concatenate([planes[n].ravel() for n in names] + list(seq[:4]))
    return dec, vals


def _unpack_chunk(dec: np.ndarray, vals: np.ndarray, names: Sequence[str],
                  K: int, M: int, P: int):
    """The fetched ``(dec, vals)`` of :func:`_pack_chunk` as ``(planes,
    accumulators)``: a dict of ``(..., K, rows)`` views (``x``/``state`` int8,
    the ``names`` planes float64) and the four accumulators as a list of
    views. Any leading axes (the gateway's slots) are kept."""
    lead = vals.shape[:-1]
    shapes = [(K, P) if n == "d_pair" else (K, M) for n in names]
    shapes += [(P,), (P,), (M,), (M,)]
    views, o = [], 0
    for s in shapes:
        n = math.prod(s)
        views.append(vals[..., o:o + n].reshape(lead + s))
        o += n
    planes = dict(zip(names, views), x=dec[..., 0, :, :],
                  state=dec[..., 1, :, :])
    return planes, views[len(names):]


def _fetch(arrays) -> list:
    """Every D2H copy of a call, all started before the first is awaited."""
    for a in arrays:
        a.copy_to_host_async()
    return [np.asarray(a) for a in arrays]


def _step_out(planes: dict) -> Dict[str, np.ndarray]:
    """The stepping outputs from unpacked ``(..., K, rows)`` planes, in
    ``(..., rows, K)`` layout (:meth:`FleetRuntime.run`'s)."""
    x, vpn_t, cci_t = planes["x"].astype(np.int64), planes["vpn_t"], planes["cci_t"]
    out = dict(x=x, state=planes["state"].astype(np.int64),
               r_vpn=planes["r_vpn"], r_cci=planes["r_cci"], vpn_cost=vpn_t,
               cci_cost=cci_t, cost=np.where(x == 1, cci_t, vpn_t))
    return {k: v.swapaxes(-1, -2) for k, v in out.items()}


def _ring_reads(t: np.ndarray, h: np.ndarray, rings, outs) -> None:
    """Gather a chunk's pre-chunk window reads from host ring twins:
    ``out[s, k, m]`` (each of ``outs`` an ``(S, K, M)`` segment of the
    caller's H2D block) is the prefix at hour ``t[s] + k - h[s, m]`` in the
    hour-major ``(S, hbuf, M)`` ring, slot 0 before hour 0. In-chunk
    positions (``k >= h``, so all ``k >= hbuf``, zero-filled) are stale;
    the device replaces them from its snapshots. Flat indices: one base
    ``((t - h) % hbuf)*M + row``, then a broadcast ``+M`` a later hour with
    a single wrap fix-up (slots advance together)."""
    S, hbuf, M = rings[0].shape
    K = outs[0].shape[1]
    Kw, span = min(K, hbuf), hbuf * M
    rows = np.arange(M)
    flat = ((t[:, None] - h) % hbuf * M + rows)[:, None, :] + np.arange(
        0, Kw * M, M)[:, None]                                    # (S, Kw, M)
    np.subtract(flat, span, out=flat, where=flat >= span)
    if min(t.tolist()) < hbuf:   # early stream: hours before 0 clip to slot 0
        flat = np.where(
            (t[:, None] + np.arange(Kw))[:, :, None] < h[:, None, :], rows, flat
        )
    if S > 1:   # index the flattened (S, hbuf, M) rings; slot 0 adds nothing
        flat += np.arange(0, S * span, span)[:, None, None]
    for ring, out in zip(rings, outs):
        np.take(ring.reshape(-1), flat, out=out[:, :Kw])
        if K > Kw:
            out[:, Kw:] = 0.0


def _ring_commit(t: np.ndarray, rings, snaps, accs, fetched_accs) -> None:
    """Mirror a chunk's device carry into the host twins: the last
    ``min(K, hbuf)`` prefix snapshots ``(S, K, M)`` (``snap[k]`` is the
    prefix BEFORE hour ``t+k``) into the ``(S, hbuf, M)`` rings at
    ``(t + k) % hbuf``, and the fetched accumulators into ``accs``. With
    ``K > hbuf`` the earlier snapshots would only be rewritten."""
    S, hbuf, M = rings[0].shape
    K = snaps[0].shape[1]
    w = min(K, hbuf)
    at = (t[:, None] + np.arange(K - w, K)) % hbuf                # (S, w)
    if S > 1:   # rows of the flattened (S * hbuf, M) rings
        at += np.arange(0, S * hbuf, hbuf)[:, None]
    for ring, snap in zip(rings, snaps):
        ring.reshape(S * hbuf, M)[at.ravel()] = snap[:, K - w:].reshape(S * w, M)
    for host, got in zip(accs, fetched_accs):
        host[...] = got


def _build_step_many_packed(
    topology: bool, pred_source: Optional[str], endo: bool,
    obs: bool = False, drain: bool = False, K: int = 1,
):
    """:func:`_build_step_many` with this variant's :func:`_packed_planes`
    packed for ONE trip home (:func:`_pack_chunk`); the device carry and the
    drained metrics ring are returned as the builder returns them."""
    step = _build_step_many(topology, pred_source, endo, obs, drain, K)
    names = _packed_planes(pred_source, obs)
    built = _chunk_planes(pred_source)

    def step_many_packed(*args):
        fsm, ssm_h, t, ring, seq, planes, drain_vec = step(*args)
        dec, vals = _pack_chunk(dict(zip(built, planes)), seq, names)
        return fsm, ssm_h, t, ring, seq, dec, vals, drain_vec

    return step_many_packed


@dataclasses.dataclass(frozen=True)
class ResolvedRuntime:
    """The operands one streaming runtime steps with, fully resolved.

    Produced by :func:`resolve_runtime_operands` — the SINGLE spec/policy
    resolution path shared by :class:`FleetRuntime` and the multi-tenant
    gateway (:mod:`repro.gateway`), so a pooled tenant and a standalone
    runtime built from the same ``(spec, RuntimeConfig)`` are guaranteed to
    price and gate on identical arrays (the lifted bit-exactness contract).
    """

    spec: object                  # the TopologySpec when one was given (for
                                  # reroute validation), else None
    topology: bool
    arrays: object                # stacked FleetArrays / TopologyArrays
    policy: object                # resolved policy pytree, per-row leaves
    pred_source: Optional[str]    # None | "replay" | "live"
    fc: Optional[dict]            # live-forecaster device params, or None
    hours_per_month: int
    routing_plan: Optional[RoutingPlan] = None  # the typed plan behind
                                  # arrays.routing in topology mode (None
                                  # for pre-stacked arrays — reconstructed
                                  # from the operand legs downstream)


def resolve_runtime_operands(spec, config: RuntimeConfig) -> ResolvedRuntime:
    """Resolve ``(spec, config)`` into stepping operands (see
    :class:`ResolvedRuntime`). Pure construction — no carried state is
    allocated here."""
    config = config.validate()
    with jax.enable_x64():
        kind = "reactive"
        hours_per_month = int(config.hours_per_month)
        resolved_spec = None
        routing_plan = None
        routing = config.routing
        if isinstance(spec, FleetSpec):
            hours_per_month = spec.hours_per_month
            kind = spec.policy
            arrays: Union[FleetArrays, TopologyArrays] = spec.stack(jnp.float64)
        elif isinstance(spec, TopologySpec):
            hours_per_month = spec.hours_per_month
            kind = spec.policy
            assert routing is not None, (
                "a TopologySpec needs an explicit routing (the runtime "
                "cannot co-optimize it online; run optimize_routing first)"
            )
            resolved_spec = spec
            routing_plan = as_routing_plan(
                routing, n_ports=spec.n_ports,
                context="FleetRuntime(routing=)",
            )
            arrays = spec.stack(routing_plan, jnp.float64)
        else:
            assert routing is None, "pre-stacked arrays already carry a routing"
            arrays = spec
        topology = isinstance(arrays, TopologyArrays)
        policy = config.policy
        if policy is None:
            policy = make_policy(
                kind, arrays.toggle, renew_in_chunks=config.renew_in_chunks
            )

        pred_source = None
        fc = None
        if isinstance(policy, ForecastGatedPolicy):
            assert policy.cost_coef is not None, (
                "streaming a ForecastGatedPolicy needs explicit demand->"
                "cost coefficients: build it with forecast_fleet_policy/"
                "forecast_topology_policy (or pass cost_coef= to "
                "forecast_gated_policy)"
            )
            if config.forecaster is not None:
                pred_source = "live"
                fc = {
                    "params": jax.tree.map(
                        jnp.asarray, config.forecaster.params
                    ),
                    "scale": jnp.asarray(config.forecaster.scale, jnp.float64),
                }
            else:
                pred_source = "replay"
                assert policy.pred_demand.ndim == 2, (
                    "replay mode indexes pred_demand columns per tick — "
                    "expected a (rows, T) prediction matrix"
                )
        else:
            assert config.forecaster is None, (
                "forecaster= only applies to a ForecastGatedPolicy"
            )
    return ResolvedRuntime(
        spec=resolved_spec,
        topology=topology,
        arrays=arrays,
        policy=policy,
        pred_source=pred_source,
        fc=fc,
        hours_per_month=int(hours_per_month),
        routing_plan=routing_plan,
    )


class FleetRuntime:
    """Incremental fleet planner: ``step(demand_t) -> modes``, one jit call.

    The streaming twin of :func:`repro.fleet.engine.plan_fleet` /
    :func:`plan_topology`: the same pricing stage, the same shared policy
    layer, but advanced one hour per call with every link/port stepped in
    one jitted vmapped tick. ``N`` calls reproduce the offline planner's
    decision sequences bit-for-bit for all three policies (the module
    docstring explains the prefix-ring construction that makes the window
    sums exact).

    Args:
      spec: a :class:`FleetSpec`/:class:`FleetArrays` (fleet routing) or
        :class:`TopologySpec`/:class:`TopologyArrays` (shared-port routing;
        give ``routing`` with a spec, or pre-stacked arrays).
      policy: a policy pytree with per-row leading axes, as the offline
        planners take. ``None`` resolves the spec's ``policy`` kind. A
        :class:`ForecastGatedPolicy` must carry explicit ``cost_coef``
        (build it with the forecast factories); its ``pred_demand`` columns
        are replayed per tick unless a ``forecaster`` puts it in live mode.
      forecaster: a :class:`StreamingForecaster` — switches the forecast
        policy to live stepping (carried SSM state, no precomputed
        predictions; required for endogenous demand).
      hours_per_month: billing calendar. Taken from the SPEC when one is
        given (the kwarg then has no effect — same contract as the offline
        planners); pass pre-stacked arrays to choose it explicitly.
      obs: observability. ``None`` (default) disables it entirely — no ring
        in the carry, no timers, the tick compiles without metrics ops.
        ``True`` or a :class:`repro.obs.observer.ObsConfig` attaches a
        :class:`repro.obs.observer.FleetObserver` (``self.obs``): device
        metrics ring drained at ``cadence``, toggle/lease event tracing,
        live contract monitors, tick profiling. Decisions are bit-identical
        either way — the ring only consumes tick outputs (property-tested).
        See :meth:`obs_report` / :meth:`obs_check`.
    """

    def __init__(
        self,
        spec,
        *,
        routing: Optional[Sequence[int]] = None,
        policy=None,
        hours_per_month: int = 730,
        renew_in_chunks: bool = False,
        forecaster: Optional[StreamingForecaster] = None,
        obs=None,
    ):
        # The kwarg surface and from_config() share one validation path:
        # everything funnels through a RuntimeConfig (kwargs keep working —
        # they ARE the config fields).
        self.config = RuntimeConfig(
            routing=routing,
            policy=policy,
            hours_per_month=hours_per_month,
            renew_in_chunks=renew_in_chunks,
            forecaster=forecaster,
            obs=obs,
        ).validate()
        ops = resolve_runtime_operands(spec, self.config)
        with jax.enable_x64():
            self._spec = ops.spec
            self.topology = ops.topology
            self.arrays = ops.arrays
            self._set_routing_caches(ops.routing_plan)
            self.policy = ops.policy
            self.pred_source = ops.pred_source
            self._fc = ops.fc
            if ops.pred_source == "live":
                self._forecaster = forecaster

            self.hours_per_month = ops.hours_per_month
            self.hbuf = int(np.max(np.asarray(self.arrays.toggle.h))) + 1
            self.n_rows = self.arrays.toggle.theta1.shape[0]
            self.n_demand_rows = (
                self.arrays.n_pairs if self.topology else self.n_rows
            )
            self._h_np = np.asarray(self.arrays.toggle.h, np.int64)[None]

            if obs is not None and obs is not False:
                from repro.obs.observer import FleetObserver, ObsConfig

                cfg = ObsConfig() if obs is True else obs
                self.obs = FleetObserver(cfg, self)
                # still under enable_x64 — the edges must stay float64
                self._obs_edges = jnp.asarray(self.obs.hist_edges, jnp.float64)
            else:
                self.obs = None
                self._obs_edges = None
            self.reset()

    @classmethod
    def from_config(cls, spec, config: RuntimeConfig) -> "FleetRuntime":
        """Build a runtime from a :class:`RuntimeConfig` — the explicit twin
        of the keyword constructor (same fields, same validation). This is
        the construction path the multi-tenant gateway uses: its
        ``TenantSpec`` embeds the same config object."""
        config = config.validate()
        return cls(
            spec,
            routing=config.routing,
            policy=config.policy,
            hours_per_month=config.hours_per_month,
            renew_in_chunks=config.renew_in_chunks,
            forecaster=config.forecaster,
            obs=config.obs,
        )

    def _set_routing_caches(self, plan: Optional[RoutingPlan] = None) -> None:
        """Host twins of ``arrays.routing`` (the single source): the typed
        :class:`RoutingPlan` behind the stacked leg operand, the (P,)
        first-hop index vector modes()/sync-group mapping consume, and the
        (M, P) membership matrix — all derived ONCE per (re)routing, never
        per tick. ``plan`` short-circuits the leg decode when the caller
        already holds the typed plan (construction from a spec, reroute)."""
        if not self.topology:
            self.routing_plan = None
            self._routing_np = self._routing_idx = self._routing_idx_np = None
            return
        if plan is None:
            # Pre-stacked arrays: the operand legs ARE the routing; decode
            # them back into the typed host view (tree_rows provenance is
            # not recoverable from weights alone, which only matters for
            # report labelling — the tick consumes the legs either way).
            plan = RoutingPlan.from_operand(
                self.arrays.routing, self.n_rows
                if hasattr(self, "n_rows")
                else int(np.asarray(self.arrays.toggle.theta1).shape[0]),
                provenance="from_operand:FleetRuntime",
            )
        self.routing_plan = plan
        self._routing_np = plan.matrix
        self._routing_idx_np = plan.primary
        self._routing_idx = jnp.asarray(self._routing_idx_np, jnp.int32)

    def _step_many_fn(self, endo: bool, drain: bool, K: int):
        key = (
            "many", self.topology, self.pred_source, endo,
            self.obs is not None, drain, K,
        )
        fn = _STEP_CACHE.get(key)
        if fn is None:
            # Donate the seq carry (arg 10) — the caller always adopts the
            # returned carry, so XLA reuses the buffers across chunks; the
            # metrics ring (arg 7) is donated for the same reason as in the
            # per-tick variant.
            fn = _STEP_CACHE.setdefault(key, jax.jit(
                _build_step_many_packed(key[1], key[2], endo,
                                        self.obs is not None, drain, K),
                donate_argnums=(7, 10) if self.obs is not None else (10,),
            ))
        return fn

    def _device_seq(self):
        """The device-resident twin of the host's sequential float64 block
        (tier cums, window prefixes, live forecast), built lazily and kept
        across chunks; the window rings stay host-only (see
        :func:`_build_step_many`). Invalidated by ``reset()``."""
        if self._dev_seq is None:
            st = self._state
            with jax.enable_x64():
                self._dev_seq = jax.device_put((
                    st.dcum, st.month_vol, st.vpn_pref, st.cci_pref,
                    st.pred_live,
                ))
        return self._dev_seq

    def reset(self) -> None:
        """Rewind to tick 0 (fresh carry; operands and policy unchanged)."""
        with jax.enable_x64():
            fsm = jax.vmap(lambda p: p.init_carry())(self.policy)
            t_dev = jnp.int32(0)
        M, P = self.n_rows, self.n_demand_rows
        z = lambda *s: np.zeros(s, np.float64)
        if self.pred_source == "live":
            ssm_h = jnp.asarray(self._forecaster.h0, jnp.float32)
            pred_live = np.asarray(self._forecaster.pred0, np.float64)
        else:
            ssm_h = jnp.zeros((M, 0), jnp.float32)
            pred_live = z(M)
        metrics = None
        if self.obs is not None:
            with jax.enable_x64():  # f64 ring fields silently downcast outside
                metrics = init_ring(
                    M, self.obs.cadence,
                    self.obs.config.hist_bins, self.obs.n_tiers,
                )
            self.obs.on_reset()
        self._state = RuntimeState(
            t=0,
            fsm=fsm,
            ssm_h=ssm_h,
            t_dev=t_dev,
            routing=self.arrays.routing if self.topology else None,
            dcum=z(P),
            month_vol=z(P),
            vpn_pref=z(M),
            cci_pref=z(M),
            ring_vpn=z(self.hbuf, M),
            ring_cci=z(self.hbuf, M),
            pred_live=pred_live,
            metrics=metrics,
        )
        self._dev_seq = None
        self._hpm_dev = jnp.int32(self.hours_per_month)

    @property
    def t(self) -> int:
        return int(self._state.t)

    def step(self, demand_t, *, cci_demand_t=None) -> Dict[str, np.ndarray]:
        """Advance one hour. ``demand_t``: (rows,) GB billed on the VPN path
        this hour (per pair in topology mode); ``cci_demand_t`` optionally
        prices the CCI counterfactual on its own volume (endogenous demand —
        the two paths carry differently-compressed traffic). Returns this
        hour's per-row decision/cost arrays; the FSM state that SERVES the
        hour is ``out["state"]`` (map it with :func:`modes`).

        This is :meth:`step_many` on a one-hour block: there is one stepping
        path, so per-tick and chunked streams are the same computation."""
        col = lambda v: np.asarray(v, np.float64)[:, None]
        out = self.step_many(
            col(demand_t),
            cci_demand_block=None if cci_demand_t is None else col(cci_demand_t),
        )
        return {k: v[:, 0] for k, v in out.items()}

    def step_many(
        self, demand_block, *, cci_demand_block=None
    ) -> Dict[str, np.ndarray]:
        """Advance K hours in ONE jitted ``lax.scan`` dispatch.

        ``demand_block`` is ``(rows, K)`` — the next K columns of the same
        (rows, T) matrix :meth:`run` takes; ``cci_demand_block`` optionally
        prices the CCI counterfactual on its own ``(rows, K)`` volume
        (endogenous demand, as in :meth:`step`). Returns :meth:`step`'s
        dict with ``(rows, K)`` stacked arrays (the :meth:`run` layout).

        Contract: ``step_many`` over any chunking of a demand stream is
        BIT-EXACT vs per-tick :meth:`step` (which is ``step_many`` on one
        hour) — decisions, window sums, and the float64 prefixes. The
        carry runs on device in one sequential order whatever the chunking
        (see :func:`_build_step_many`) and the host mirrors it at chunk
        boundaries, so per-tick and chunked stepping interleave freely and
        :meth:`reroute` at a chunk boundary behaves exactly as it does
        between two ``step()`` calls. With observability on, the drain
        cadence must not fall strictly inside a chunk (pick K dividing the
        cadence, or break the stream at the boundary): drains then fire at
        the same hours with bit-identical windows, as a third D2H array.

        Everything else comes home as two device buffers (see
        :func:`_build_step_many_packed`): the int8 decision planes and one
        flat float64 vector of the cost planes and accumulators, both
        copies started before either is awaited, and the host cuts its
        arrays out of them as views.

        The call is the ``fleet.step`` span of :mod:`repro.obs.profile`, cut
        into ``pack``, ``dispatch``, ``wait``, ``fetch`` and ``mirror``.
        """
        with span("fleet.step"):
            with span("fleet.step.pack"):
                st = self._state
                t = st.t
                P = self.n_demand_rows
                d = np.asarray(demand_block, np.float64)
                assert d.ndim == 2 and d.shape[0] == P, (
                    f"demand_block must be (rows, K) = ({P}, K), got {d.shape}"
                )
                K = d.shape[1]
                assert K >= 1, K
                endo = cci_demand_block is not None
                block = self._pack(st, d, cci_demand_block)
                drain = False
                if self.obs is not None:
                    cadence = self.obs.cadence
                    boundary = ((t // cadence) + 1) * cadence  # 1st drain > t
                    assert boundary >= t + K, (
                        f"obs drain cadence {cadence} falls mid-chunk (hour "
                        f"{boundary} inside ({t}, {t + K})): chunk ends must "
                        f"align with the drain cadence — pick K dividing the "
                        f"cadence, or step() across the boundary"
                    )
                    drain = boundary == t + K
            with span("fleet.step.dispatch"):
                fn = self._step_many_fn(endo, drain, K)
                with jax.enable_x64():
                    fsm, ssm_h, t_dev, ring, seq, dec, vals, drain_vec = fn(
                        self.arrays, self.policy, self._fc, st.fsm, st.ssm_h,
                        st.t_dev, st.routing, st.metrics, self._obs_edges,
                        self._hpm_dev, self._device_seq(),
                        jax.device_put(block),
                    )
                self._dev_seq = seq
            with span("fleet.step.wait"):
                jax.block_until_ready((dec, vals))
            with span("fleet.step.fetch"):
                # Every D2H copy of the call: the int8 decisions, the packed
                # float64 vector and, on drain calls, the metrics ring.
                fetched = _fetch((dec, vals, drain_vec) if drain else (dec, vals))
                count("fleet.step.d2h_arrays", len(fetched))
                count("fleet.step.h2d_bytes", block.nbytes)
                count("fleet.step.d2h_bytes", sum(a.nbytes for a in fetched))

            with span("fleet.step.mirror"):
                p, accs = _unpack_chunk(
                    fetched[0], fetched[1],
                    _packed_planes(self.pred_source, self.obs is not None),
                    K, self.n_rows, P,
                )                                           # (K, rows) planes
                _ring_commit(
                    np.asarray([t]), (st.ring_vpn[None], st.ring_cci[None]),
                    (p["snap_v"][None], p["snap_c"][None]),
                    (st.dcum, st.month_vol, st.vpn_pref, st.cci_pref), accs,
                )
                self._state = st._replace(
                    t=t + K, fsm=fsm, ssm_h=ssm_h, t_dev=t_dev,
                    pred_live=(
                        p["pred"][-1].copy() if self.pred_source == "live"
                        else st.pred_live
                    ),
                    metrics=ring,
                )
                out = _step_out(p)
        if self.obs is not None:
            self.obs.record_chunk(
                t,
                [{f: v[:, k] for f, v in out.items()} for k in range(K)],
                d_pair=p["d_pair"], demand=d, endo=endo,
            )
            if drain:
                self.obs.record_drain(t + K, fetched[-1])
        return out

    def _pack(self, st: RuntimeState, d: np.ndarray, cci_demand_block):
        """The chunk's single flat H2D block (see :func:`_build_step_many`):
        the (rows, K) demand [and CCI demand], raveled in their native order
        (the device transposes where it fuses anyway), then the pre-chunk
        window reads as two (K, rows) planes, gathered in place by
        :func:`_ring_reads`."""
        M, P = self.n_rows, self.n_demand_rows
        K = d.shape[1]
        nd = (1 if cci_demand_block is None else 2) * K * P
        block = np.empty(nd + 2 * K * M)
        block[:K * P] = d.ravel()
        if cci_demand_block is not None:
            c = np.asarray(cci_demand_block, np.float64)
            assert c.shape == d.shape, (c.shape, d.shape)
            block[K * P:nd] = c.ravel()
        pre = block[nd:].reshape(2, 1, K, M)
        _ring_reads(np.asarray([st.t]), self._h_np,
                    (st.ring_vpn[None], st.ring_cci[None]), pre)
        return block

    def run(self, demand, *, cci_demand=None) -> Dict[str, np.ndarray]:
        """Convenience: stream a whole (rows, T) matrix tick by tick and stack
        the outputs into the offline planners' (rows, T) layout."""
        demand = np.asarray(demand)
        outs = []
        for t in range(demand.shape[1]):
            outs.append(self.step(
                demand[:, t],
                cci_demand_t=None if cci_demand is None else cci_demand[:, t],
            ))
        return {
            k: np.stack([np.asarray(o[k]) for o in outs], axis=1) for k in outs[0]
        }

    def reroute(self, routing) -> None:
        """Swap the row→port routing MID-STREAM (topology mode only).

        ``routing`` is a :class:`repro.fleet.routing.RoutingPlan` — any hop
        depth or tree shape whose padded leg bound fits the one the stream
        was compiled with (``plan.total_hops <= n_legs`` at construction;
        a larger plan raises :class:`ValueError` rather than silently
        recompiling). Legacy bare ``(P,)`` index vectors and ``(M, P)``
        one-hot matrices keep working through the :func:`as_routing_plan`
        deprecation shim. The swap is a pure operand change on the carried
        :class:`RuntimeState`: the compiled tick is reused, and every piece
        of carried state — FSM carries, float64 prefix rings (so window
        sums near the swap mix old- and new-routing hours, as a live system
        experiences them), pair billing state, SSM forecaster state — rides
        across untouched. Contract: decisions from this tick on are
        bit-exact vs :func:`repro.fleet.engine.replay_plan_topology` with
        the same routing applied at the same hour.

        Compute the new routing however you like — e.g.
        :func:`repro.fleet.topology.optimize_routing` /
        ``refine_routing``-style moves on the demand means observed so far
        (see ``examples/reroute_demo.py`` for live re-routing on streamed
        state).
        """
        assert self.topology, (
            "reroute() applies to topology (shared-port) mode; a fleet has "
            "no routing to swap"
        )
        old_idx = self._routing_idx_np.copy()
        M, P = self.n_rows, self.n_demand_rows
        with jax.enable_x64():
            plan = as_routing_plan(
                routing, n_ports=M, context="FleetRuntime.reroute"
            )
            assert plan.n_rows == P, (
                f"plan routes {plan.n_rows} rows, stream carries {P}"
            )
            if self._spec is not None:
                self._spec.validate_plan(plan)
            E = int(self.arrays.routing.leg_pair.shape[-1])
            if plan.total_hops > E:
                raise ValueError(
                    f"plan needs {plan.total_hops} legs but the stream was "
                    f"compiled with a padded bound of {E} — rerouting at a "
                    "deeper bound would recompile the tick. Construct the "
                    "runtime with a routing pad_to()'d to the maximum hop "
                    "budget you plan to swap in."
                )
            plan = plan.pad_to(E)
            op = plan.operand(jnp.float64)
        self.arrays = self.arrays._replace(routing=op)  # keep views coherent
        self._set_routing_caches(plan)
        self._state = self._state._replace(routing=op)
        if self.obs is not None:
            self.obs.record_reroute(
                self.t, old_idx, self._routing_idx_np, plan=self.routing_plan
            )

    # --- observability surface (only when built with obs=) ------------------

    def _flush_obs(self) -> None:
        """Drain a partial metrics window host-side (one extra D2H — only at
        report/check time, never on the per-tick hot path)."""
        if self.obs is None:
            return
        ring = self._state.metrics
        if int(ring.small[0]) == 0:
            return
        with jax.enable_x64():
            vec = np.asarray(flatten_ring(ring))
            self._state = self._state._replace(metrics=reset_ring(ring))
        self.obs.record_drain(self.t, vec)

    def obs_report(self):
        """Flush pending metrics and build the :class:`repro.obs.ObsReport`
        (aggregate counters, cost quantiles, tick-latency profile, monitor
        summaries). Requires the runtime to have been built with ``obs=``."""
        assert self.obs is not None, "runtime built without obs="
        self._flush_obs()
        return self.obs.report()

    def obs_check(self, *, final: bool = True) -> None:
        """Flush pending metrics and run every enabled contract monitor NOW,
        raising :class:`repro.obs.ContractViolation` on the first breach.
        ``final=True`` additionally arms end-of-run-only checks (regret
        bounds that are meaningless mid-stream)."""
        assert self.obs is not None, "runtime built without obs="
        self._flush_obs()
        self.obs.check(final=final)

    def port_occupancy(self) -> np.ndarray:
        """(M,) pairs attached per port under the CURRENT routing (all-ones
        in fleet mode — one link per row)."""
        if not self.topology:
            return np.ones(self.n_rows)
        return np.bincount(
            self._routing_idx_np, minlength=self.n_rows
        ).astype(np.float64)

    def modes(self, out, *, mode_fn=None) -> list:
        """Map one step's FSM states to per-ACTUATOR collective modes.

        Fleet mode: one mode per link (decision row == actuator). Topology
        mode: one mode per PAIR — each pair inherits its routed port's FSM
        state under the current routing, because the actuation surface
        (:func:`repro.dist.collectives.fleet_sync_grads`) syncs per training
        job (pair), not per decision row; pairs sharing an ON port share one
        leased sync domain.

        ``mode_fn`` maps an FSM state code to a mode string; ``None`` falls
        back to the module-level :func:`repro.core.planner.collective_mode`
        (the deprecated global default —
        :class:`ElasticFleetPlanner` passes its per-instance one).
        """
        if mode_fn is None:
            mode_fn = collective_mode
        states = np.asarray(out["state"])
        if self.topology:
            states = states[self._routing_idx_np]
        return [mode_fn(int(s)) for s in states]


# ---------------------------------------------------------------------------
# Actuation: the endogenous-demand planner over the runtime
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FleetPlannerReport:
    """Realized economics of an actuated streaming run.

    Rows are DECISION rows (links in fleet mode, ports in topology mode);
    actuator-level columns (``pair_gb``/``pair_gb_saved``) are per pair ==
    per link in fleet mode. ``port_occupancy`` is the per-PORT lease
    occupancy under the final routing (pairs attached; all-ones in fleet
    mode) — decision rows no longer map 1:1 onto actuators.
    """

    hours: int
    total_cost: float
    cost_always_vpn: float
    cost_always_cci: float
    on_fraction: np.ndarray        # (M,) fraction of hours the row leased
    total_gb: float
    link_cost: np.ndarray          # (M,) realized cost per decision row
    port_occupancy: np.ndarray     # (M,) pairs attached per port/link
    pair_gb: np.ndarray            # (P,) billed GB per pair/link
    pair_gb_saved: np.ndarray      # (P,) wire GB saved vs always-full-precision

    @property
    def wire_savings_fraction(self) -> float:
        """Fleet-wide fraction of raw wire GB the compressed path saved."""
        raw = self.pair_gb.sum() + self.pair_gb_saved.sum()
        return float(self.pair_gb_saved.sum() / raw) if raw > 0 else 0.0


class ElasticFleetPlanner:
    """N-row :class:`repro.core.planner.InterconnectPlanner`.

    feed_hour(bytes) per tick; FSM modes actuate the collective layer
    (``'hierarchical'`` over the leased link at full precision,
    ``'compressed'`` int8+error-feedback on the pay-per-GB path), and each
    mode's counterfactual is priced on ITS OWN demand shape: the VPN path
    carries ~4x fewer billed GB (the endogenous loop — pricing both on the
    served volume creates the hysteresis trap documented in core.planner).

    Two routings, like the runtime underneath: *fleet* mode feeds per-LINK
    bytes and returns per-link modes; *per-port topology* mode (build with a
    ``TopologySpec`` + ``routing=``, or routed ``TopologyArrays``) feeds
    per-PAIR bytes, prices SHARED port leases through the routed core, and
    returns per-pair modes — pairs sharing an ON port form one leased sync
    domain (pass the port ids as ``groups=`` to
    :func:`repro.dist.collectives.fleet_sync_grads` to fuse their syncs),
    with wire bytes still metered per pair via ``sync_wire_bytes``.
    Re-routing mid-stream (``.runtime.reroute``) re-targets the actuation
    on the next tick.
    """

    # Deprecated default: prefer the per-instance ``compress_ratio=``
    # constructor parameter; this class attribute (aliasing the module-level
    # global in repro.core.planner) remains only as its fallback value.
    COMPRESS_RATIO = COMPRESS_RATIO

    def __init__(
        self,
        fleet,
        *,
        compress_ratio: Optional[float] = None,
        collective_mode=None,
        **runtime_kw,
    ):
        """``compress_ratio``/``collective_mode`` are per-instance knobs
        (different planners can price different compression hardware or map
        FSM states to custom collective paths). ``None`` falls back to the
        module-level globals in :mod:`repro.core.planner`, which are
        retained as deprecated defaults only."""
        self.runtime = FleetRuntime(fleet, **runtime_kw)
        self.topology = self.runtime.topology
        self.compress_ratio = float(compress_ratio or self.COMPRESS_RATIO)
        self.collective_mode = (
            collective_mode if collective_mode is not None
            else globals()["collective_mode"]
        )
        n, p = self.runtime.n_rows, self.runtime.n_demand_rows
        self.cost = np.zeros(n)
        self.cost_vpn_only = np.zeros(n)
        self.cost_cci_only = np.zeros(n)
        self.gb = np.zeros(p)
        self.gb_saved = np.zeros(p)
        self.on_hours = np.zeros(n, np.int64)
        self._dom_sig = None  # last (groups, modes) signature traced

    def sync_groups(self) -> np.ndarray:
        """(P,) leased-sync-domain id per actuator: the routed port index in
        topology mode (pairs sharing a port share one domain), own row in
        fleet mode. Feed as ``groups=`` to ``fleet_sync_grads``."""
        if not self.topology:
            return np.arange(self.runtime.n_rows)
        return self.runtime._routing_idx_np.copy()

    def feed_hour(self, cross_pod_bytes) -> list:
        """Account one hour of per-actuator cross-pod traffic (bytes; per
        link in fleet mode, per PAIR in topology mode). Returns each
        actuator's collective mode for the hour just served."""
        raw_gb = np.asarray(cross_pod_bytes, np.float64) / 1e9
        out = self.runtime.step(
            raw_gb / self.compress_ratio, cci_demand_t=raw_gb
        )
        x = np.asarray(out["x"])
        on = x == 1
        vpn_c = np.asarray(out["vpn_cost"])
        cci_c = np.asarray(out["cci_cost"])
        self.cost += np.where(on, cci_c, vpn_c)
        self.cost_vpn_only += vpn_c
        self.cost_cci_only += cci_c
        modes = self.runtime.modes(out, mode_fn=self.collective_mode)
        if self.runtime.obs is not None:
            # Sync-domain fusion change events: a domain is a (port, mode)
            # bucket of actuators; trace only when the partition changes.
            groups = self.sync_groups()
            sig = (groups.tobytes(), "".join(m[0] for m in modes))
            if sig != self._dom_sig:
                n_dom = len(set(zip(groups.tolist(), modes)))
                self.runtime.obs.record_sync_domains(
                    self.runtime.t - 1, n_dom, len(modes)
                )
                self._dom_sig = sig
        on_act = np.asarray([m == "hierarchical" for m in modes])
        self.gb += np.where(on_act, raw_gb, raw_gb / self.compress_ratio)
        self.gb_saved += np.where(on_act, 0.0, raw_gb - raw_gb / self.compress_ratio)
        self.on_hours += on
        return modes

    def report(self) -> FleetPlannerReport:
        h = self.runtime.t
        return FleetPlannerReport(
            hours=h,
            total_cost=float(self.cost.sum()),
            cost_always_vpn=float(self.cost_vpn_only.sum()),
            cost_always_cci=float(self.cost_cci_only.sum()),
            on_fraction=self.on_hours / max(1, h),
            total_gb=float(self.gb.sum()),
            link_cost=self.cost.copy(),
            port_occupancy=self.runtime.port_occupancy(),
            pair_gb=self.gb.copy(),
            pair_gb_saved=self.gb_saved.copy(),
        )


def streaming_forecast_policy(
    arrays,
    history,
    *,
    margin=0.05,
    hours_per_month: int = 730,
    renew_in_chunks: bool = False,
    **train_kw,
):
    """Build a live-mode forecast policy + its streaming forecaster.

    Fully causal: the SSM head trains on the (rows, H) ``history`` block and
    the demand→cost coefficients are fitted on history-derived cost series —
    nothing about the live horizon is needed up front. Returns ``(policy,
    forecaster)`` for ``FleetRuntime(..., policy=policy,
    forecaster=forecaster)``. ``arrays`` may be fleet or (routed) topology
    arrays; topology histories are per PAIR and aggregated here exactly as
    the engine aggregates demand.
    """
    from .engine import routed_cost_series
    from .policy import fit_cost_coef, forecast_gated_policy, forecast_horizon_hours

    history = np.asarray(history, np.float64)
    window = forecast_horizon_hours(arrays.toggle)
    with jax.enable_x64():
        hist = jnp.asarray(history, jnp.float64)
        s = routed_cost_series(arrays, hist, hours_per_month=hours_per_month)
        coef = fit_cost_coef(s.row_demand, s.vpn, s.cci)
        agg = np.asarray(s.row_demand)
    fc = StreamingForecaster.fit(agg, window, **train_kw)
    rows = agg.shape[0]
    policy = forecast_gated_policy(
        arrays.toggle,
        np.zeros(rows),  # unused in live mode (pred comes from the SSM state)
        margin=margin,
        cost_coef=np.asarray(coef),
        renew_in_chunks=renew_in_chunks,
    )
    return policy, fc
