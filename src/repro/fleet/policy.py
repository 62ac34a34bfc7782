"""Pluggable toggle policies: the decision layer of every CCI planner.

The paper's ToggleCCI (§VI) is one *policy* — a reactive FSM over sliding
window counterfactual costs. Before this module, that FSM was hard-fused
into three separate scan bodies (``run_togglecci_scan``, the fleet plan fn,
the topology plan fn); adding any new decision rule meant triplicating it.
Now every planner calls ONE shared :func:`policy_scan` kernel with the
policy as a *vmapped pytree operand*:

* :class:`ReactivePolicy`     — the paper's FSM, bit-for-bit (the float64
  reference path :func:`repro.fleet.engine.plan_topology_reference` stays
  the exactness oracle for this policy);
* :class:`HysteresisPolicy`   — reactive plus consecutive-hour hold counts
  on both transitions (a cheap debouncing ablation; hold=1 degenerates to
  :class:`ReactivePolicy` exactly);
* :class:`ForecastGatedPolicy`— an SSM head (:mod:`repro.models.ssm`)
  trained on per-port demand history predicts demand over the next
  ``D + T_cci`` window; lease requests fire *early* when predicted savings
  clear a confidence margin, and realized triggers are *suppressed* when
  the forecast says the cost trend is transient. This is the ROADMAP's
  "forecast-driven toggling": ToggleCCI's reactivity pays the full
  provisioning delay at VPN prices on every regime shift, and the report's
  oracle-gap column prices exactly what prediction can recover (cf. Pied
  Piper / CORNIFER, which provision virtual WAN capacity ahead of need).

Protocol (duck-typed; every policy is a registered pytree whose CHILDREN
are arrays — so one compiled scan serves any parameter values and
``jax.vmap`` maps it over heterogeneous fleets — while static knobs like
``renew_in_chunks`` live in the treedef aux data, keeping them out of the
hot scan):

* ``toggle``                  — a :class:`~repro.core.togglecci.ToggleParams`
  (θ₁/θ₂/h/D/T_cci as traceable scalars);
* ``init_carry()``            — initial scan carry;
* ``features(demand, vpn_hourly, cci_hourly)`` — per-hour extras scanned
  alongside the window sums (``None`` for memoryless policies);
* ``step(carry, (r_vpn, r_cci), extras_t)`` — one FSM transition, returns
  ``(carry', (x_t, state_t))``.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.togglecci import OFF, ON, WAITING, ToggleParams, window_sums

POLICY_KINDS = ("reactive", "hysteresis", "forecast")


def _pytree_policy(array_fields: Tuple[str, ...]):
    """Register a policy dataclass as a pytree: ``array_fields`` become
    children (traceable, vmappable), every other field is static aux data
    baked into the treedef — and therefore into the compiled program, so a
    static ``renew_in_chunks`` costs nothing inside the scan (a traced flag
    measurably slowed the 8760-step hot loop)."""

    def wrap(cls):
        cls = dataclasses.dataclass(frozen=True)(cls)
        static_fields = tuple(
            f.name for f in dataclasses.fields(cls) if f.name not in array_fields
        )

        def flatten(self):
            return (
                tuple(getattr(self, n) for n in array_fields),
                tuple(getattr(self, n) for n in static_fields),
            )

        def unflatten(aux, children):
            return cls(**dict(zip(array_fields, children)),
                       **dict(zip(static_fields, aux)))

        jax.tree_util.register_pytree_node(cls, flatten, unflatten)
        return cls

    return wrap


def _fsm_cascade(tp: ToggleParams, renew_in_chunks: bool, carry, req_cond, rel_cond):
    """The paper's OFF→WAITING→ON cascade with pluggable trigger conditions.

    Exactly the transition spec of :func:`repro.core.togglecci.run_togglecci`
    (start-of-hour transitions, ``t_state`` counts hours served in-state) —
    only the OFF→WAITING request condition and the ON→OFF release condition
    are injected by the policy; ``renew_in_chunks`` is a STATIC bool (part
    of the policy treedef).
    """
    state, t_state = carry

    go_wait = (state == OFF) & req_cond
    s1 = jnp.where(go_wait, WAITING, state)
    ts1 = jnp.where(go_wait, 0, t_state)

    wait_done = (s1 == WAITING) & (ts1 >= tp.D)
    s2 = jnp.where(wait_done, ON, s1)
    ts2 = jnp.where(wait_done, 0, ts1)

    past_commit = ts2 >= tp.T_cci
    at_renewal = (ts2 % tp.T_cci) == 0
    check = past_commit & at_renewal if renew_in_chunks else past_commit
    go_off = (s2 == ON) & check & rel_cond
    s3 = jnp.where(go_off, OFF, s2)
    ts3 = jnp.where(go_off, 0, ts2)

    x_t = jnp.where(s3 == ON, 1, 0)
    return (s3, ts3 + 1), (x_t, s3)


@_pytree_policy(("toggle",))
class ReactivePolicy:
    """The paper's ToggleCCI decision rule, unchanged.

    Request when the trailing window says CCI would have been cheap
    (``R_CCI < θ₁·R_VPN``); release when it says CCI turned expensive
    (``R_CCI > θ₂·R_VPN``). Through :func:`policy_scan` this reproduces the
    pre-policy-layer planners bit-for-bit (property-tested in
    ``tests/test_policy.py``).
    """

    kind = "reactive"  # plain attr (not a field): obs/report labelling

    toggle: ToggleParams
    renew_in_chunks: bool = False  # static: release only at T_cci multiples

    def init_carry(self):
        return (jnp.int32(OFF), jnp.int32(0))

    def features(self, demand, vpn_hourly, cci_hourly):
        return None

    def step(self, carry, window, extras):
        r_vpn, r_cci = window
        tp = self.toggle
        req = r_cci < tp.theta1 * r_vpn
        rel = r_cci > tp.theta2 * r_vpn
        return _fsm_cascade(tp, self.renew_in_chunks, carry, req, rel)


@_pytree_policy(("toggle", "up_hold", "down_hold"))
class HysteresisPolicy:
    """Reactive thresholds debounced by consecutive-hour hold counts.

    A request (release) fires only after its window condition has held for
    ``up_hold`` (``down_hold``) consecutive hours — asymmetric dwell on top
    of the θ₁/θ₂ hysteresis, the classic cheap fix for threshold chatter.
    ``up_hold = down_hold = 1`` is exactly :class:`ReactivePolicy`.
    """

    kind = "hysteresis"

    toggle: ToggleParams
    up_hold: jax.Array    # int32 ≥ 1 — consecutive hours before requesting
    down_hold: jax.Array  # int32 ≥ 1 — consecutive hours before releasing
    renew_in_chunks: bool = False

    def init_carry(self):
        return (jnp.int32(OFF), jnp.int32(0), jnp.int32(0), jnp.int32(0))

    def features(self, demand, vpn_hourly, cci_hourly):
        return None

    def step(self, carry, window, extras):
        state, t_state, up, down = carry
        r_vpn, r_cci = window
        tp = self.toggle
        raw_req = r_cci < tp.theta1 * r_vpn
        raw_rel = r_cci > tp.theta2 * r_vpn
        up = jnp.where(raw_req, up + 1, 0)
        down = jnp.where(raw_rel, down + 1, 0)
        req = raw_req & (up >= self.up_hold)
        rel = raw_rel & (down >= self.down_hold)
        (s, ts), out = _fsm_cascade(
            tp, self.renew_in_chunks, (state, t_state), req, rel
        )
        return (s, ts, up, down), out


_LOG_COST_EPS = 1e-9  # idle rows (no routed pairs) have zero cost series


def fit_cost_coef(demand, vpn_hourly, cci_hourly):
    """Log-space demand→cost maps, least-squares on the first half.

    ``(..., T)`` inputs → ``(..., 4)`` coefficients ``[a_vpn, b_vpn, a_cci,
    b_cci]`` such that ``cost ≈ exp(a + b·log1p(demand))``. The pricing
    *function* is static, so this is structure recovery, not lookahead. The
    fit is MULTIPLICATIVE deliberately: an affine fit of the TIERED
    (concave) VPN cost extrapolated outside its support crosses zero, and a
    predicted ``p_vpn ≈ 0`` blows the predicted cost ratio up to hundreds —
    the release gate ``p_cci > (θ₂+m)·p_vpn`` then fires whatever the
    margin (the mirage −103% forecast_gain failure mode; the log-space map
    keeps ratios bounded and positive, measured ≈ 0% there with the same
    gates). Shared by the in-scan fallback of
    :meth:`ForecastGatedPolicy.features` and the eager factories (which bake
    the coefficients into the policy so the streaming runtime
    (:mod:`repro.fleet.runtime`) never needs the full series).
    """
    T = vpn_hourly.shape[-1]
    fit_T = max(T // 2, 2)
    x = jnp.log1p(demand[..., :fit_T])
    xm = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - xm) ** 2, axis=-1)

    def loglin(y):
        y0 = jnp.log(jnp.maximum(y[..., :fit_T], _LOG_COST_EPS))
        cov = jnp.mean((x - xm) * (y0 - jnp.mean(y0, axis=-1, keepdims=True)), axis=-1)
        beta = jnp.where(var > 1e-12, cov / jnp.maximum(var, 1e-12), 0.0)
        return jnp.mean(y0, axis=-1) - beta * xm[..., 0], beta

    av, bv = loglin(vpn_hourly)
    ac, bc = loglin(cci_hourly)
    return jnp.stack([av, bv, ac, bc], axis=-1)


def predicted_mode_costs(pred, cost_coef, dtype):
    """Map predicted demand through the log-space fit → (pred_vpn, pred_cci).

    Elementwise, so the offline scan (full ``(T,)`` rows) and the streaming
    runtime (one tick) produce bit-identical gate inputs.
    """
    lp = jnp.log1p(pred.astype(dtype))
    coef = cost_coef.astype(dtype)
    pred_vpn = jnp.exp(coef[..., 0] + coef[..., 1] * lp)
    pred_cci = jnp.exp(coef[..., 2] + coef[..., 3] * lp)
    return pred_vpn, pred_cci


@_pytree_policy(("toggle", "margin", "pred_demand", "cost_coef"))
class ForecastGatedPolicy:
    """SSM-forecast-gated ToggleCCI.

    ``pred_demand[t]`` is the forecaster's causal estimate of mean demand
    over the next ``D + T_cci``-ish window, made from history through hour
    ``t-1`` (see :func:`forecast_port_demand`). :meth:`features` converts it
    to predicted per-hour mode costs through affine demand→cost maps
    (:func:`fit_cost_coef`): ``cost_coef`` carries them explicitly (the
    factories fit them eagerly — required by the streaming runtime, which
    never sees the full series); with ``cost_coef=None`` the fit happens
    inside :meth:`features` on the realized series, the original in-scan
    behavior. The gates:

    * request  — forecast alone fires early when confidently cheap
      (``p_cci < (θ₁ − m)·p_vpn``), or the realized trigger fires and the
      forecast does not confidently object (``p_cci < (θ₁ + m)·p_vpn`` —
      objection suppresses transient spikes);
    * release  — symmetric: confidently-expensive forecast alone
      (``p_cci > (θ₂ + m)·p_vpn``), or realized with no confident objection
      (``p_cci > (θ₂ − m)·p_vpn`` — suppresses releases in transient dips,
      which would otherwise re-pay the provisioning delay).

    The margin therefore interpolates between trusting the forecast (m → 0:
    hard confirmation gates) and pure reactive ToggleCCI (m → ∞: forecast
    can neither fire nor veto) — at m = 0 both forms coincide. ``margin``
    is per-row (per link/port) because fleets mixing demand families need
    different settings: on growth traces (mirage) reactive is already near
    the oracle and the affine cost map is biased by tier drift, so a hard
    veto *creates* spurious releases — measured −103% forecast_gain before
    the slack, ≈ −0% at mirage's wide margin (see :data:`FAMILY_MARGINS`),
    while bursty keeps its large gain under a tight one.
    """

    kind = "forecast"

    toggle: ToggleParams
    margin: jax.Array       # confidence margin m ≥ 0 on the forecast gates
    pred_demand: jax.Array  # (T,) causal forward-window mean demand, GB/hr
    cost_coef: object = None  # (4,) [a_vpn, b_vpn, a_cci, b_cci] or None
    renew_in_chunks: bool = False

    def init_carry(self):
        return (jnp.int32(OFF), jnp.int32(0))

    def features(self, demand, vpn_hourly, cci_hourly):
        if self.cost_coef is not None:
            return predicted_mode_costs(
                self.pred_demand, self.cost_coef, vpn_hourly.dtype
            )
        assert demand is not None, (
            "ForecastGatedPolicy needs the demand series to map predicted "
            "demand to predicted mode costs (or pass explicit cost_coef)"
        )
        coef = fit_cost_coef(demand, vpn_hourly, cci_hourly)
        return predicted_mode_costs(self.pred_demand, coef, vpn_hourly.dtype)

    def step(self, carry, window, extras):
        r_vpn, r_cci = window
        p_vpn, p_cci = extras
        tp, m = self.toggle, self.margin
        req = (p_cci < (tp.theta1 - m) * p_vpn) | (
            (r_cci < tp.theta1 * r_vpn) & (p_cci < (tp.theta1 + m) * p_vpn)
        )
        rel = (p_cci > (tp.theta2 + m) * p_vpn) | (
            (r_cci > tp.theta2 * r_vpn) & (p_cci > (tp.theta2 - m) * p_vpn)
        )
        return _fsm_cascade(tp, self.renew_in_chunks, carry, req, rel)


# ---------------------------------------------------------------------------
# The shared scan kernel — the ONLY place FSM decisions are unrolled in time
# ---------------------------------------------------------------------------


def policy_scan(policy, vpn_hourly: jax.Array, cci_hourly: jax.Array, *, demand=None):
    """Run one toggle policy over per-hour mode costs with ``lax.scan``.

    The single FSM kernel behind :func:`repro.core.togglecci.run_togglecci_scan`,
    :func:`repro.fleet.engine.plan_fleet` and
    :func:`repro.fleet.engine.plan_topology` — vmap it (policy included) over
    link/port axes for fleets.

    Args:
      policy: a :data:`POLICY_KINDS` pytree (see module docstring).
      vpn_hourly, cci_hourly: (T,) per-hour counterfactual mode costs.
      demand: optional (T,) demand series handed to ``policy.features``
        (required by :class:`ForecastGatedPolicy`, ignored by the others).
    Returns:
      dict with ``x`` (T,), ``state`` (T,), ``r_vpn``/``r_cci`` window sums,
      ``total_cost`` scalar — the exact contract the planners consume.
    """
    tp = policy.toggle
    with jax.named_scope("window_sums"):
        r_vpn_tr = window_sums(vpn_hourly, tp.h)
        r_cci_tr = window_sums(cci_hourly, tp.h)
    extras = policy.features(demand, vpn_hourly, cci_hourly)

    def step(carry, xs):
        window, ex = xs
        return policy.step(carry, window, ex)

    with jax.named_scope("fsm_scan"):
        _, (x, state_tr) = jax.lax.scan(
            step, policy.init_carry(), ((r_vpn_tr, r_cci_tr), extras)
        )
    acc = r_vpn_tr.dtype
    total = jnp.sum(
        jnp.where(x == 1, cci_hourly.astype(acc), vpn_hourly.astype(acc))
    )
    return {
        "x": x,
        "state": state_tr,
        "r_vpn": r_vpn_tr,
        "r_cci": r_cci_tr,
        "total_cost": total,
    }


# ---------------------------------------------------------------------------
# Factories (spec threading + convenience)
# ---------------------------------------------------------------------------


def reactive_policy(
    toggle: ToggleParams, *, renew_in_chunks: bool = False
) -> ReactivePolicy:
    return ReactivePolicy(toggle=toggle, renew_in_chunks=bool(renew_in_chunks))


def hysteresis_policy(
    toggle: ToggleParams,
    *,
    up_hold: int = 6,
    down_hold: int = 6,
    renew_in_chunks: bool = False,
) -> HysteresisPolicy:
    shape = jnp.shape(toggle.theta1)
    return HysteresisPolicy(
        toggle=toggle,
        up_hold=jnp.full(shape, up_hold, jnp.int32),
        down_hold=jnp.full(shape, down_hold, jnp.int32),
        renew_in_chunks=bool(renew_in_chunks),
    )


def forecast_gated_policy(
    toggle: ToggleParams,
    pred_demand,
    *,
    margin=0.05,
    cost_coef=None,
    renew_in_chunks: bool = False,
) -> ForecastGatedPolicy:
    """Wrap forward-window demand predictions as a gated policy.

    ``margin`` is a scalar or a per-row array matching ``toggle.theta1``
    (per-link/port confidence bars — see :func:`family_margins`).
    ``cost_coef`` (rows, 4) bakes the demand→cost affine maps in; ``None``
    defers the fit to scan time (offline planners only — the streaming
    runtime requires explicit coefficients).
    """
    f = jnp.result_type(float)
    return ForecastGatedPolicy(
        toggle=toggle,
        margin=jnp.broadcast_to(
            jnp.asarray(margin, f), jnp.shape(toggle.theta1)
        ),
        pred_demand=jnp.asarray(pred_demand, f),
        cost_coef=None if cost_coef is None else jnp.asarray(cost_coef, f),
        renew_in_chunks=bool(renew_in_chunks),
    )


def make_policy(kind: str, toggle: ToggleParams, *, renew_in_chunks=False, **kw):
    """Build a policy by name — the ``FleetSpec.policy`` / ``TopologySpec.policy``
    selection hook the engines resolve when no policy object is passed."""
    if kind == "reactive":
        assert not kw, f"reactive policy takes no extra options, got {kw}"
        return reactive_policy(toggle, renew_in_chunks=renew_in_chunks)
    if kind == "hysteresis":
        return hysteresis_policy(toggle, renew_in_chunks=renew_in_chunks, **kw)
    if kind == "forecast":
        raise ValueError(
            "the forecast policy needs a trained forecaster: build it with "
            "forecast_fleet_policy(...) / forecast_topology_policy(...) (or "
            "forecast_gated_policy on your own predictions) and pass it as "
            "policy=... to the planner"
        )
    raise ValueError(f"unknown toggle policy {kind!r} (known: {POLICY_KINDS})")


# Per-family confidence margins for the forecast gates. One scalar margin
# cannot serve a mixed fleet: stationary/bursty families tolerate a tight
# bar (and bursty thrives on it), while mirage's user-growth traces need a
# wider one — reactive is already near the oracle there, so the forecast
# should only act when confident (the ROADMAP's mirage forecast_gain
# regression; see the ForecastGatedPolicy docstring for the gate
# semantics). Values measured by `bench_policy` margin sweeps
# (48 pairs x 8760 h per family, seed 0): mirage −0.7% at 0.05 vs +1.3-1.4%
# on the 0.10-0.15 plateau; the others are flat across 0.02-0.10.
FAMILY_MARGINS = {
    "constant": 0.05,
    "bursty": 0.05,
    "mirage": 0.15,
    "puffer": 0.05,
}


def family_margins(families, *, default: float = 0.05, overrides=None) -> np.ndarray:
    """Per-row confidence margins from demand-family labels.

    ``families`` is one label per link/port row (e.g. ``[l.family for l in
    fleet.links]``); unknown labels fall back to ``default``. Returns a
    (rows,) float array for the ``margin=`` argument of the forecast-policy
    factories.
    """
    table = dict(FAMILY_MARGINS)
    if overrides:
        table.update(overrides)
    return np.asarray([table.get(f, default) for f in families], np.float64)


# ---------------------------------------------------------------------------
# Forecast construction: SSM head over demand history
# ---------------------------------------------------------------------------


def forecast_horizon_hours(toggle: ToggleParams) -> int:
    """The fleet-wide forecast window: mean ``D + T_cci`` over links/ports.

    One shared window (the forecaster is trained once per fleet) — per-link
    windows differ but the gate compares predicted cost *ratios*, where the
    window length cancels; only the smoothing scale matters.
    """
    return int(
        np.mean(np.asarray(toggle.D, np.float64) + np.asarray(toggle.T_cci, np.float64))
    )


def forecast_port_demand(
    history,
    live,
    window: int,
    *,
    state_dim: int = 8,
    steps: int = 300,
    lr: float = 2e-2,
    seed: int = 0,
) -> np.ndarray:
    """Causal forward-window demand forecasts for every row of ``live``.

    Trains the :mod:`repro.models.ssm` demand forecaster on ``history``
    (N, H) — strictly disjoint, earlier hours — then runs it over
    ``concat(history, live)`` so that ``pred[:, t]`` (the predicted mean
    demand over live hours ``[t, t+window)``) uses demand strictly before
    live hour ``t``. With ``history=None`` the first half of ``live`` is
    used for fitting instead (documented in-sample compromise for callers
    without a warm-up trace; predictions stay causal either way).
    """
    from repro.models.ssm import demand_forecaster_predict, train_demand_forecaster

    live = np.asarray(live, np.float64)
    n, T = live.shape
    if history is None:
        train = live[:, : max(T // 2, 2)]
        full = live
        offset = 0
    else:
        history = np.asarray(history, np.float64)
        assert history.shape[0] == n, (history.shape, live.shape)
        train = history
        full = np.concatenate([history, live], axis=1)
        offset = history.shape[1]

    params, scale = train_demand_forecaster(
        train, window, state_dim=state_dim, steps=steps, lr=lr, seed=seed
    )
    y = demand_forecaster_predict(params, full, scale)
    # y[:, j] predicts the window starting at hour j+1 using full[:, :j+1];
    # live hour t = full hour offset+t, so its forecast is y[:, offset+t-1].
    pred = np.empty((n, T))
    if offset > 0:
        pred[:] = y[:, offset - 1 : offset - 1 + T]
    else:
        pred[:, 1:] = y[:, : T - 1]
        pred[:, 0] = np.asarray(scale)  # no history: predict the fit mean
    return pred


def forecast_fleet_policy(
    arrays,
    demand,
    history=None,
    *,
    margin=0.05,
    hours_per_month: int = 730,
    renew_in_chunks=False,
    **train_kw,
) -> ForecastGatedPolicy:
    """Train the SSM head on per-link demand history and wrap it as a policy.

    ``arrays`` is a :class:`~repro.fleet.spec.FleetArrays`; ``demand``/
    ``history`` are (N, T)/(N, H) GB/hr (clipped at link capacity here, as
    the engine does). The demand→cost coefficients are fitted eagerly on the
    engine's own cost series (:func:`repro.fleet.engine.routed_cost_series`)
    and baked into the policy, so the streaming runtime can gate on them
    without ever seeing the full horizon.
    """

    from .engine import routed_cost_series

    cap = np.asarray(arrays.capacity, np.float64)[:, None]
    clip = lambda d: np.minimum(np.asarray(d, np.float64), cap)
    pred = forecast_port_demand(
        None if history is None else clip(history),
        clip(demand),
        forecast_horizon_hours(arrays.toggle),
        **train_kw,
    )
    with jax.enable_x64():
        s = routed_cost_series(
            arrays,
            jnp.asarray(demand, jnp.float64),
            hours_per_month=hours_per_month,
        )
        coef = fit_cost_coef(s.row_demand, s.vpn, s.cci)
    return forecast_gated_policy(
        arrays.toggle, pred, margin=margin, cost_coef=coef,
        renew_in_chunks=renew_in_chunks,
    )


def forecast_topology_policy(
    arrays,
    demand,
    history=None,
    *,
    margin=0.05,
    hours_per_month: int = 730,
    renew_in_chunks=False,
    **train_kw,
) -> ForecastGatedPolicy:
    """Per-PORT forecast policy: aggregate pair demand onto routed ports first.

    ``arrays`` is a routed :class:`~repro.fleet.topology.TopologyArrays`;
    aggregation mirrors the engine (VLAN access clip per pair, hard CCI clip
    on the port aggregate), so the forecaster sees exactly the series whose
    costs the port FSM toggles on — ROADMAP: "forecast each port's
    aggregate, not each pair". Cost coefficients are fitted eagerly on the
    engine's port-aggregated series and baked into the policy (streaming-
    runtime ready), exactly as in :func:`forecast_fleet_policy`.
    """

    from .engine import routed_cost_series

    # Multi-hot (M, P) membership matrix off the routing operand's legs —
    # a multi-hop row contributes its demand to EVERY hop's aggregate,
    # exactly like the engine's leg-list segment_sum.
    op = arrays.routing
    R = np.zeros(
        (int(np.asarray(arrays.L_cci).shape[0]),
         int(np.asarray(arrays.L_vpn).shape[0]))
    )
    np.add.at(
        R,
        (np.asarray(op.leg_port), np.asarray(op.leg_pair)),
        np.asarray(op.attach_w, np.float64),
    )
    pair_cap = np.asarray(arrays.pair_capacity, np.float64)[:, None]
    port_cap = np.asarray(arrays.port_capacity, np.float64)[:, None]
    agg = lambda d: np.minimum(
        R @ np.minimum(np.asarray(d, np.float64), pair_cap), port_cap
    )
    pred = forecast_port_demand(
        None if history is None else agg(history),
        agg(demand),
        forecast_horizon_hours(arrays.toggle),
        **train_kw,
    )
    with jax.enable_x64():
        s = routed_cost_series(
            arrays,
            jnp.asarray(demand, jnp.float64),
            hours_per_month=hours_per_month,
        )
        coef = fit_cost_coef(s.row_demand, s.vpn, s.cci)
    return forecast_gated_policy(
        arrays.toggle, pred, margin=margin, cost_coef=coef,
        renew_in_chunks=renew_in_chunks,
    )
