"""The harness end to end on the CPU, at a small size.

Every cell of ``BENCHMARK.json`` runs. Each cell's run passes the end of its
demand year inside the window (the streams wrap it with ``reset()``; a plan
call decides all of it), and still equals the reference; the result line
holds the contract's keys; a run without an accelerator prints nothing and
fails.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.harness import Registry
from bench.tests.conftest import REPO, SEED, run_tiny

CELLS = [w["name"] for w in Registry(REPO).bench["workloads"]]
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_equals_reference_past_the_horizon(tiny_root, workload):
    r = run_tiny(tiny_root, workload, seconds=1.5)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r) == RESULT_KEYS                      # the checks come last
    assert set(r["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    wanted = {m["name"] for m in bench["end_to_end"]
              if workload in m.get("workloads", [workload])}
    assert set(r["metrics"]) == wanted
    assert all(set(m) == {"value", "unit"} and m["value"] > 0 for m in r["metrics"].values())
    # The rows and hours of the year the kind builds at its tiny size.
    registry = Registry(tiny_root)
    cell = registry.cell(workload)
    rows, year = registry.kind(cell).build(registry.config(cell), SEED).demand.shape
    hours = r["metrics"]["row_hours_per_s"]["value"] * 1.5 / rows
    assert hours > year, "the window never passed the end of the demand year"


def _run_py(root, *args, **env):
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"), *args],
        cwd=root, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def test_run_without_accelerator_fails_and_prints_nothing():
    p = _run_py(REPO, "--workload", "fleet2048.stream_k24", "--seed", str(2**31 + 3),
                "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "accelerator" in p.stderr


def test_run_outside_a_checkout_fails_and_prints_nothing(tmp_path):
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("_cache", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    env = {"PYTHONPATH": ""}
    p = _run_py(str(tmp_path), "--workload", "fleet2048.plan", "--seed", "1",
                "--seconds", "1", "--trace", "0", **env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
