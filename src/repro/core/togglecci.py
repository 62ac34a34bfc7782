"""ToggleCCI — the paper's online algorithm (§VI, Fig. 5).

A three-state controller (OFF → WAITING → ON) driven by sliding-window
counterfactual costs:

* ``R_VPN`` — what the last ``h`` hours *would have cost* entirely over VPN;
* ``R_CCI`` — ditto entirely over CCI.

Transitions (hysteresis thresholds θ₁ < θ₂, paper defaults 0.9 / 1.1):

* OFF:      route VPN;  if ``R_CCI < θ₁·R_VPN``  → request CCI, enter WAITING.
* WAITING:  route VPN for the provisioning delay ``D`` hours, then → ON.
* ON:       route CCI;  committed for at least ``T_CCI`` hours; afterwards,
            if ``R_CCI > θ₂·R_VPN`` → release CCI, return to OFF.

During the warm-up ``t < h`` the window is the partial prefix (paper: "uses
the cumulative cost from the past t steps only").

Renewal semantics: the paper's §VI text implies a *continuous* stay-condition
check after the first commitment, while Fig. 12(c) narrates renewal in
``T_CCI``-sized chunks. Both are implemented; ``renew_in_chunks=False``
(continuous) is the default. Tests cover both.

Two equivalent implementations:
* :func:`run_togglecci`      — pure-Python reference, returns rich diagnostics.
* :func:`run_togglecci_scan` — ``jax.lax.scan`` version (jit/vmap-able across
  scenario batches; used by the sensitivity benchmarks and the planner).
  Since the policy-layer refactor this is a thin wrapper over the shared
  :func:`repro.fleet.policy.policy_scan` kernel with a ``ReactivePolicy`` —
  the same kernel the fleet and topology planners call with pluggable
  policies (forecast-gated, hysteresis).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .costmodel import HourlyCosts, hourly_cost_series, prefix_sum
from .pricing import CostParams

OFF, WAITING, ON = 0, 1, 2
STATE_NAMES = {OFF: "OFF", WAITING: "WAITING", ON: "ON"}


@dataclasses.dataclass
class ToggleResult:
    x: np.ndarray            # (T,) 0/1 — CCI actually serving traffic at hour t
    state: np.ndarray        # (T,) FSM state during hour t
    r_vpn: np.ndarray        # (T,) sliding-window VPN counterfactual cost
    r_cci: np.ndarray        # (T,) sliding-window CCI counterfactual cost
    requests: list           # hours at which CCI provisioning was requested
    releases: list           # hours at which CCI was released
    total_cost: float
    costs: HourlyCosts


def run_togglecci(
    params: CostParams,
    demand: np.ndarray,
    *,
    costs: Optional[HourlyCosts] = None,
    renew_in_chunks: bool = False,
) -> ToggleResult:
    """Pure-Python reference implementation of ToggleCCI."""
    costs = costs if costs is not None else hourly_cost_series(params, demand)
    T = costs.vpn.shape[0]
    h, D, T_cci = params.h, params.D, params.T_cci

    vpn_pref = np.concatenate([[0.0], np.cumsum(costs.vpn)])
    cci_pref = np.concatenate([[0.0], np.cumsum(costs.cci)])

    x = np.zeros(T, dtype=np.int64)
    state_trace = np.zeros(T, dtype=np.int64)
    r_vpn_tr = np.zeros(T)
    r_cci_tr = np.zeros(T)
    requests, releases = [], []

    # Transition spec (shared exactly with the scan version): at the START of
    # hour t, observe the window [max(0, t-h), t), apply at most the cascade
    # OFF->WAITING, WAITING->ON (covers D=0), ON->OFF; then serve hour t in the
    # resulting state. ``t_state`` counts hours already served in the state, so
    # WAITING serves exactly D VPN hours and ON serves >= T_cci CCI hours.
    state, t_state = OFF, 0
    for t in range(T):
        lo = max(0, t - h)
        r_vpn = vpn_pref[t] - vpn_pref[lo]
        r_cci = cci_pref[t] - cci_pref[lo]
        r_vpn_tr[t], r_cci_tr[t] = r_vpn, r_cci

        if state == OFF and r_cci < params.theta1 * r_vpn:
            state, t_state = WAITING, 0
            requests.append(t)
        if state == WAITING and t_state >= D:
            state, t_state = ON, 0
        if state == ON and t_state >= T_cci:
            at_renewal = (t_state % params.T_cci) == 0
            if (at_renewal if renew_in_chunks else True) and (
                r_cci > params.theta2 * r_vpn
            ):
                state, t_state = OFF, 0
                releases.append(t)

        state_trace[t] = state
        x[t] = 1 if state == ON else 0
        t_state += 1

    total = float(np.sum(np.where(x == 1, costs.cci, costs.vpn)))
    return ToggleResult(
        x=x, state=state_trace, r_vpn=r_vpn_tr, r_cci=r_cci_tr,
        requests=requests, releases=releases, total_cost=total, costs=costs,
    )


# ---------------------------------------------------------------------------
# lax.scan implementation
# ---------------------------------------------------------------------------


class ToggleParams(NamedTuple):
    """ToggleCCI's decision parameters as *traceable array operands*.

    Unlike :class:`CostParams` (whose fields are Python scalars baked into
    the trace), every field here is a jax scalar — so one compiled scan can
    be ``vmap``-ped over a fleet of heterogeneous links (see ``repro.fleet``)
    with per-link thresholds, windows, delays and commitments.
    """

    theta1: jax.Array  # OFF->WAITING threshold
    theta2: jax.Array  # ON->OFF threshold
    h: jax.Array       # sliding window, hours (int32)
    D: jax.Array       # provisioning delay, hours (int32)
    T_cci: jax.Array   # minimum commitment, hours (int32)

    @classmethod
    def from_cost_params(cls, p: CostParams) -> "ToggleParams":
        f = jnp.result_type(float)
        return cls(
            theta1=jnp.asarray(p.theta1, f),
            theta2=jnp.asarray(p.theta2, f),
            h=jnp.asarray(p.h, jnp.int32),
            D=jnp.asarray(p.D, jnp.int32),
            T_cci=jnp.asarray(p.T_cci, jnp.int32),
        )


def window_sums(hourly: jax.Array, h) -> jax.Array:
    """Sliding-window sums ``r[t] = sum(hourly[max(0, t-h):t])``.

    This is ToggleCCI's cost-trend signal. The series it consumes is
    whatever granularity the caller decides on: the paper's single link, a
    fleet link (:func:`repro.fleet.engine.plan_fleet`), or a *port-aggregated*
    counterfactual summed over every region pair routed through one CCI port
    (:func:`repro.fleet.engine.plan_topology`) — the FSM is agnostic, it
    only ever sees the two (T,) series.

    Computed from prefix sums OUTSIDE the scan (the FSM scan itself is pure
    integer arithmetic). Precision: year-long float32 cumsums reach ~1e6-1e7
    while hourly costs sit at ~1e0-1e3, so float32 prefix differences can
    flip θ₁/θ₂ comparisons near the threshold. Concrete inputs therefore
    take a float64 numpy path unconditionally; traced inputs accumulate in
    ``jnp.result_type(float)`` — float64 whenever the caller runs under
    x64 (the fleet engine does), float32 otherwise.
    """
    if not isinstance(hourly, jax.core.Tracer) and not isinstance(
        h, jax.core.Tracer
    ):
        v = np.asarray(hourly, dtype=np.float64)
        T = v.shape[0]
        pref = np.concatenate([[0.0], np.cumsum(v)])
        t_idx = np.arange(T)
        lo = np.maximum(0, t_idx - int(h))
        r = pref[t_idx] - pref[lo]
        return jnp.asarray(r.astype(np.result_type(jnp.result_type(float))))
    acc = jnp.result_type(float)
    v = hourly.astype(acc)
    T = v.shape[0]
    with jax.named_scope("prefix_scan"):
        pref = jnp.concatenate([jnp.zeros(1, acc), prefix_sum(v)])
    t_idx = jnp.arange(T)
    lo = jnp.maximum(0, t_idx - h)
    return pref[t_idx] - pref[lo]


def run_togglecci_scan(
    params,
    vpn_hourly: jax.Array,
    cci_hourly: jax.Array,
    *,
    renew_in_chunks: bool = False,
):
    """``lax.scan`` ToggleCCI over precomputed per-hour mode costs.

    A thin wrapper over the shared policy kernel: the FSM body lives ONCE in
    :func:`repro.fleet.policy.policy_scan`, parameterized by a
    :class:`~repro.fleet.policy.ReactivePolicy` (this function IS the
    reactive policy entry point; other policies plug into the same kernel).

    Args:
      params: :class:`CostParams` (static Python scalars) or
        :class:`ToggleParams` (traceable array operands — required when
        vmapping over heterogeneous links).
      vpn_hourly, cci_hourly: (T,) per-hour counterfactual costs.
    Returns:
      dict with ``x`` (T,), ``state`` (T,), ``r_vpn``/``r_cci`` window
      sums, ``total_cost`` scalar.

    vmap over leading scenario/link axes by vmapping this function (map the
    ``ToggleParams`` fields too for heterogeneous fleets).
    """
    # The policy layer sits above core (it extends core's FSM); import
    # lazily so the module graph stays acyclic at import time.
    from repro.fleet.policy import policy_scan, reactive_policy

    tp = (
        params
        if isinstance(params, ToggleParams)
        else ToggleParams.from_cost_params(params)
    )
    pol = reactive_policy(tp, renew_in_chunks=renew_in_chunks)
    return policy_scan(pol, vpn_hourly, cci_hourly)
