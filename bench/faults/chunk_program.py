"""Faults planted in the streams' chunk program,
``repro.fleet.runtime._build_step_many``: each step it builds is wrapped so
that its outputs carry the fault, and the runtime's cache of built steps is
emptied so that the wrapped builder is the one used."""
from __future__ import annotations


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


def plant(monkeypatch, fault: str) -> None:
    from repro.fleet import runtime

    monkeypatch.setattr(runtime, "_STEP_CACHE", {})
    monkeypatch.setattr(runtime, "_build_step_many", _chunk_fault(fault, runtime._build_step_many))
