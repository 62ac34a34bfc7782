"""Each fault a cell can have, planted under the timed path, makes the run
come out not correct: a step that hands back its state unchanged, half of
the rows left out, one answer altered where it is produced. (No cell spans
chips, so none has an exchange between chips to leave out.)

Each cell's kind lists in ``FAULTS`` the programs its timed path runs, and
the faults are planted in each of them by its planter,
``bench/faults/<name>.py``, found by name: ``chunk_program``, the streams'
``_build_step_many``, and ``plan_program``, the plan's ``_run_plan``. The
faults wrap those, so the drivers, the program's host code and the
comparison run as they do in a benchmark run.
"""
from __future__ import annotations

import pytest

from bench.harness import Registry
from bench.tests.conftest import run_tiny
from bench.tests.test_harness import CELLS


@pytest.mark.parametrize("fault", ["state_unchanged", "half_rows_left_out", "answer_altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_planted_fault_is_not_correct(tiny_root, monkeypatch, workload, fault):
    registry = Registry(tiny_root)
    families = registry.kind(registry.cell(workload)).FAULTS
    assert families, "the cell's kind names no program to plant a fault in"
    for family in families:
        registry.planter(family).plant(monkeypatch, fault)
    r = run_tiny(tiny_root, workload, seconds=0.5)
    assert not r["correct"], r["checks"]
    assert r["failed"] > 0
