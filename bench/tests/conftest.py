"""A small copy of the benchmark, for runs of its harness on the CPU.

``tiny_root`` copies ``BENCHMARK.json`` and ``bench/`` into a temporary
directory and shrinks each configuration there to its kind's ``TINY`` sizes
(``bench/kinds/<kind>.py``), so a whole run of a cell, reference and all,
takes seconds on the CPU. The harness is driven with
``require_accelerator=False`` and no compile cache.
"""
from __future__ import annotations

import json
import os
import shutil
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEED = 2**31 + 11


def make_tiny(dst: str) -> str:
    from bench.harness import Registry

    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("_cache", "__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    registry = Registry(dst)
    for entry in registry.bench["configs"]:
        path = os.path.join(dst, entry["file"])
        with open(path) as f:
            cfg = json.load(f)
        cfg.update({k: v for k, v in registry.kind_of(cfg).TINY.items() if k in cfg})
        with open(path, "w") as f:
            json.dump(cfg, f)
    return dst


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny(str(tmp_path))


def run_tiny(root: str, workload: str, *, seconds: float = 1.0, seed: int = SEED,
             program=None, trace: bool = False) -> dict:
    from bench.harness import Registry, run_cell

    return run_cell(Registry(root), workload, seed, seconds, trace,
                    t_start=time.perf_counter(), program=program,
                    require_accelerator=False, compile_cache=False)
