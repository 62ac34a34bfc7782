"""Each fault a cell can have, planted under the timed path, makes the run
come out not correct: a step that hands back its state unchanged, half of
the rows left out, one answer altered where it is produced. (No cell spans
chips, so none has an exchange between chips to leave out.)

Each cell's kind lists in ``FAULTS`` the programs its timed path runs, and
the faults are planted in each of them: ``chunk_program``, the streams'
``_build_step_many``, and ``plan_program``, the plan's ``_run_plan``. The
faults wrap those, so the drivers, the program's host code and the
comparison run as they do in a benchmark run.
"""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from bench.harness import Registry
from bench.tests.conftest import run_tiny
from bench.tests.test_harness import CELLS


def _chunk_fault(fault, build):
    def broken_build(*a, **kw):
        step = build(*a, **kw)

        def step_many(arrays, policy, fc, fsm, ssm_h, t, routing, ring, edges, hpm, seq, block):
            out = step(arrays, policy, fc, fsm, ssm_h, t, routing, ring, edges, hpm, seq, block)
            fsm1, ssm1, t1, ring1, seq1, planes, dv = out
            if fault == "state_unchanged":
                return fsm, ssm_h, t, ring1, seq, planes, dv
            if fault == "half_rows_left_out":
                m = planes[0].shape[-1]
                planes = tuple(p.at[..., m // 2:].set(0) for p in planes)
            if fault == "answer_altered":
                planes = (planes[0].at[-1, 0].set(1 - planes[0][-1, 0]),) + tuple(planes[1:])
            return fsm1, ssm1, t1, ring1, seq1, planes, dv

        return step_many

    return broken_build


def _plan_fault(fault, run_plan):
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


def _plant_in_chunk_program(monkeypatch, fault):
    from repro.fleet import runtime

    monkeypatch.setattr(runtime, "_STEP_CACHE", {})
    monkeypatch.setattr(runtime, "_build_step_many", _chunk_fault(fault, runtime._build_step_many))


def _plant_in_plan_program(monkeypatch, fault):
    from repro.fleet import engine

    monkeypatch.setattr(engine, "_JIT_CACHE", {})
    monkeypatch.setattr(engine, "_run_plan", _plan_fault(fault, engine._run_plan))


PLANT = {"chunk_program": _plant_in_chunk_program, "plan_program": _plant_in_plan_program}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_rows_left_out", "answer_altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_planted_fault_is_not_correct(tiny_root, monkeypatch, workload, fault):
    registry = Registry(tiny_root)
    families = registry.kind(registry.cell(workload)).FAULTS
    assert families, "the cell's kind names no program to plant a fault in"
    for family in families:
        PLANT[family](monkeypatch, fault)
    r = run_tiny(tiny_root, workload, seconds=0.5)
    assert not r["correct"], r["checks"]
    assert r["failed"] > 0
