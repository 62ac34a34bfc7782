"""Faults planted in the plan program, ``repro.fleet.engine._run_plan``:
its outputs carry the fault, and the engine's cache of jitted plans is
emptied so that the wrapped program is the one compiled."""
from __future__ import annotations


def _plan_fault(fault, run_plan):
    import jax.numpy as jnp

    def broken(arrays, demand, policy, hours_per_month, use_pallas=False):
        out = dict(run_plan(arrays, demand, policy, hours_per_month, use_pallas))
        if fault == "state_unchanged":           # the FSM never leaves OFF
            out["x"], out["state"] = jnp.zeros_like(out["x"]), jnp.zeros_like(out["state"])
        if fault == "half_rows_left_out":
            n = out["x"].shape[0]
            for k in ("x", "state", "toggle_cost"):
                out[k] = out[k].at[n // 2:].set(0)
        if fault == "answer_altered":
            out["toggle_cost"] = out["toggle_cost"].at[0].multiply(1 + 1e-6)
        return out

    return broken


def plant(monkeypatch, fault: str) -> None:
    from repro.fleet import engine

    monkeypatch.setattr(engine, "_JIT_CACHE", {})
    monkeypatch.setattr(engine, "_run_plan", _plan_fault(fault, engine._run_plan))
