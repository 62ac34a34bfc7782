"""The control, the reference computed in float32 in the program's place,
comes out not correct in every cell (``bench/control.py``)."""
from __future__ import annotations

import pytest

from bench import control
from bench.tests.conftest import run_tiny
from bench.tests.test_harness import CELLS


@pytest.mark.parametrize("workload", CELLS)
def test_float32_control_is_not_correct(tiny_root, workload):
    r = run_tiny(tiny_root, workload, seconds=0.5, program=control)
    assert not r["correct"], r["checks"]
    assert r["failed"] > 0
    failing = [n for n, c in r["checks"].items() if not c["value"] <= c["limit"]]
    assert failing, r["checks"]
