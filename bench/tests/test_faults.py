"""Each fault a cell can have, planted under the timed path, makes the run
come out not correct: a step that hands back its state unchanged, half of
the rows left out, one answer altered where it is produced. (No cell spans
chips, so none has an exchange between chips to leave out.)

The stream cells' chunk program is ``_build_step_many``; the plan cell's is
``_run_plan``. The faults wrap
those, so the drivers, the program's host code and the comparison run as
they do in a benchmark run.
"""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from bench.tests.conftest import run_tiny
from bench.tests.test_harness import CELLS


def _chunk_fault(kind, build):
    def broken_build(*a, **kw):
        step = build(*a, **kw)

        def step_many(arrays, policy, fc, fsm, ssm_h, t, routing, ring, edges, hpm, seq, block):
            out = step(arrays, policy, fc, fsm, ssm_h, t, routing, ring, edges, hpm, seq, block)
            fsm1, ssm1, t1, ring1, seq1, planes, dv = out
            if kind == "state_unchanged":
                return fsm, ssm_h, t, ring1, seq, planes, dv
            if kind == "half_rows_left_out":
                m = planes[0].shape[-1]
                planes = tuple(p.at[..., m // 2:].set(0) for p in planes)
            if kind == "answer_altered":
                planes = (planes[0].at[-1, 0].set(1 - planes[0][-1, 0]),) + tuple(planes[1:])
            return fsm1, ssm1, t1, ring1, seq1, planes, dv

        return step_many

    return broken_build


def _plan_fault(kind, run_plan):
    def broken(arrays, demand, policy, hours_per_month, use_pallas=False):
        out = dict(run_plan(arrays, demand, policy, hours_per_month, use_pallas))
        if kind == "state_unchanged":            # the FSM never leaves OFF
            out["x"], out["state"] = jnp.zeros_like(out["x"]), jnp.zeros_like(out["state"])
        if kind == "half_rows_left_out":
            n = out["x"].shape[0]
            for k in ("x", "state", "toggle_cost"):
                out[k] = out[k].at[n // 2:].set(0)
        if kind == "answer_altered":
            out["toggle_cost"] = out["toggle_cost"].at[0].multiply(1 + 1e-6)
        return out

    return broken


@pytest.mark.parametrize("kind", ["state_unchanged", "half_rows_left_out", "answer_altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_planted_fault_is_not_correct(tiny_root, monkeypatch, workload, kind):
    from repro.fleet import engine, runtime

    monkeypatch.setattr(runtime, "_STEP_CACHE", {})
    monkeypatch.setattr(engine, "_JIT_CACHE", {})
    broken = _chunk_fault(kind, runtime._build_step_many)
    monkeypatch.setattr(runtime, "_build_step_many", broken)
    monkeypatch.setattr(engine, "_run_plan", _plan_fault(kind, engine._run_plan))
    r = run_tiny(tiny_root, workload, seconds=0.5)
    assert not r["correct"], r["checks"]
    assert r["failed"] > 0
