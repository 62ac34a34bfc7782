"""The control of each cell's kind (``CONTROL`` of ``bench/kinds/<kind>.py``;
for a ``fleet``, the reference computed in float32, ``bench/control.py``),
put in the program's place, comes out not correct in every cell."""
from __future__ import annotations

import pytest

from bench.harness import Registry
from bench.tests.conftest import run_tiny
from bench.tests.test_harness import CELLS


@pytest.mark.parametrize("workload", CELLS)
def test_float32_control_is_not_correct(tiny_root, workload):
    registry = Registry(tiny_root)
    control = registry.kind(registry.cell(workload)).CONTROL
    r = run_tiny(tiny_root, workload, seconds=0.5, program=control)
    assert not r["correct"], r["checks"]
    assert r["failed"] > 0
    failing = [n for n, c in r["checks"].items() if not c["value"] <= c["limit"]]
    assert failing, r["checks"]
