"""Shared benchmark utilities: timing, CSV rows, result persistence, and
the persistent compilation cache of the benchmark and smoke entry points."""
from __future__ import annotations

import json
import os
import time

RESULTS_DIR = os.environ.get("REPRO_RESULTS", "results/bench")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX takes the directory from
    it and nothing is configured here. Otherwise the cache lives at the
    fixed ``<repo>/.jax_cache`` (git-ignored): the path is part of what the
    cache is keyed on, so it must not move between runs. Entry points call
    this before their first compile; library code never does.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def save_rows(name: str, rows):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(rows, f, indent=1, default=float)
    return path


def write_bench_artifact(name: str, rows):
    """Write ``BENCH_{name}.json`` for CI: uploaded as a workflow artifact
    and consumed by ``benchmarks.check_regression`` (throughput gate).
    Directory override via ``BENCH_ARTIFACT_DIR`` (default: CWD)."""
    out_dir = os.environ.get("BENCH_ARTIFACT_DIR", ".")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(rows, f, indent=1, default=float)
    return path


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, (time.perf_counter() - t0) * 1e6  # microseconds


def fmt_csv(name: str, us: float, derived) -> str:
    return f"{name},{us:.0f},{derived}"
