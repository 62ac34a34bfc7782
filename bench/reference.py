"""Plain numpy ToggleCCI: the reference that decides ``correct``.

A copy, kept with the benchmark, of the float64 references of the program:
``hourly_cost_series`` and ``monthly_cumsum_np`` of
``src/repro/core/costmodel.py``, ``run_togglecci`` of
``src/repro/core/togglecci.py`` and ``plan_fleet_reference`` of
``src/repro/fleet/engine.py``. It imports nothing of the program.

One departure in form, none in meaning: ``run_togglecci`` walks one link
at a time, this walks all links together, hour by hour, with the same
transitions in the same order, so that a year of 2048 links takes about a
second instead of forty.

Semantics (paper section VI): demand is clipped at the link's capacity;
VPN transfer is billed on tiers of the month-to-date volume, summed from
zero at each ``hours_per_month`` boundary; ``r_vpn[t]``/``r_cci[t]`` are
the costs of the window ``[max(0, t - h), t)``; the FSM goes OFF -> WAITING
when ``r_cci < theta1 * r_vpn``, WAITING -> ON after ``D`` hours, and ON ->
OFF after at least ``T_cci`` hours when ``r_cci > theta2 * r_vpn``.

``dtype`` is the precision of every sum and product: float64 is the
reference; float32 is the control that must come out not correct.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

OFF, WAITING, ON = 0, 1, 2


def monthly_cumsum(d: np.ndarray, hours_per_month: int) -> np.ndarray:
    """Month-to-date volume at the start of each hour, from zero each month."""
    out = np.zeros_like(d)
    for s in range(0, d.shape[-1], hours_per_month):
        e = min(s + hours_per_month, d.shape[-1])
        out[..., s + 1:e] = np.cumsum(d[..., s:e - 1], axis=-1)
    return out


def hourly_costs(a, demand: np.ndarray, dtype=np.float64):
    """Clipped demand and the hourly VPN and CCI costs of every link."""
    c = lambda x: np.asarray(x, dtype)
    d = np.minimum(c(demand), c(a.capacity)[:, None])
    lo = monthly_cumsum(d, a.hours_per_month)
    bounds, rates = c(a.tier_bounds), c(a.tier_rates)
    transfer = np.zeros_like(d)
    prev = np.zeros((a.n, 1), dtype)
    for j in range(bounds.shape[1]):
        b = bounds[:, j:j + 1]
        seg = np.maximum(np.minimum(np.minimum(d, b - prev),
                                    np.minimum(b - lo, d - (prev - lo))), 0)
        transfer = transfer + np.where(seg > 0, seg * rates[:, j:j + 1], 0)
        prev = b
    vpn = c(a.L_vpn)[:, None] + transfer
    cci = (c(a.L_cci) + c(a.V_cci))[:, None] + c(a.c_cci)[:, None] * d
    return d, vpn, cci


def window_sums(hourly: np.ndarray, h: np.ndarray) -> np.ndarray:
    """``r[:, t] = sum(hourly[:, max(0, t - h):t])`` as a prefix difference."""
    n, T = hourly.shape
    pref = np.concatenate([np.zeros((n, 1), hourly.dtype),
                           np.cumsum(hourly, axis=1)], axis=1)
    t = np.arange(T)
    lo = np.maximum(0, t[None, :] - h[:, None])
    return pref[:, :T] - np.take_along_axis(pref, lo, axis=1)


def toggle(a, r_vpn: np.ndarray, r_cci: np.ndarray, dtype=np.float64):
    """The ToggleCCI FSM over every link; returns (x, state) as int8.

    The two threshold tests of each hour are made up front, in ``dtype``;
    the transitions then apply them in ``run_togglecci``'s order."""
    n, T = r_vpn.shape
    req = np.ascontiguousarray((r_cci < np.asarray(a.theta1, dtype)[:, None] * r_vpn).T)
    rel = np.ascontiguousarray((r_cci > np.asarray(a.theta2, dtype)[:, None] * r_vpn).T)
    state = np.full(n, OFF, np.int64)
    dwell = np.zeros(n, np.int64)
    states = np.empty((T, n), np.int8)
    for t in range(T):
        go = (state == OFF) & req[t]
        state = np.where(go, WAITING, state)
        dwell = np.where(go, 0, dwell)
        go = (state == WAITING) & (dwell >= a.D)
        state = np.where(go, ON, state)
        dwell = np.where(go, 0, dwell)
        go = (state == ON) & (dwell >= a.T_cci) & rel[t]
        state = np.where(go, OFF, state)
        dwell = np.where(go, 0, dwell) + 1
        states[t] = state
    states = np.ascontiguousarray(states.T)
    return (states == ON).astype(np.int8), states


def run(a, demand: np.ndarray, dtype=np.float64) -> Dict[str, np.ndarray]:
    """Every per-hour output of the controller, for every link and hour of
    ``demand`` (hours only depend on earlier ones, so a prefix of the year
    gives that prefix of the outputs)."""
    d, vpn, cci = hourly_costs(a, demand, dtype)
    r_vpn, r_cci = window_sums(vpn, a.h), window_sums(cci, a.h)
    x, state = toggle(a, r_vpn, r_cci, dtype)
    cost = np.where(x == 1, cci, vpn)
    return {"x": x, "state": state, "r_vpn": r_vpn, "r_cci": r_cci,
            "vpn_cost": vpn, "cci_cost": cci, "cost": cost, "gb": d,
            "toggle_cost": cost.sum(axis=1)}
