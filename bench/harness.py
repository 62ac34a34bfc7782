"""One run of one cell: set up, measure a window, check it, report it.

Everything that belongs to one configuration, traffic mix or metric is found
by its name in ``BENCHMARK.json``:

* a configuration's sizes in the file its entry names (``bench/configs/``),
  and its kind in ``bench/kinds/<kind>.py``, by the file's ``"kind"`` key
  (``fleet`` where it names none). The kind builds the scenario from the seed,
  computes the reference the checks take, and names the program's entry
  points (``SUT``) and their lower-precision control (``CONTROL``);
* a traffic mix in ``bench/traffic/<name>.json``, whose ``driver`` names the
  loop that drives the program (``bench/drivers/<driver>.py``) and whose
  other keys are that loop's parameters. A driver's ``call()`` is one timed
  call of the entry point, ``keep()`` files its outputs for the comparison
  after the window, outside the call's time;
* a metric's reader in ``bench/metrics/<name>.py``: ``read(run)`` returns the
  number, or ``None`` where the run holds nothing to read. A metric with no
  ``workloads`` list is read in every cell;
* a fault planter in ``bench/faults/<name>.py``, by the names in a kind's
  ``FAULTS``: ``plant(monkeypatch, fault)`` breaks that timed program for the
  tests (``bench/tests/test_faults.py``).

So a later change adds a configuration of a new kind, a mix, a driver, a
metric or a planter by adding files and entries, never by editing one that
is there.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

from bench import tracing
from bench.compile_log import CompileLog, use_compile_cache


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Registry:
    """``BENCHMARK.json`` and the files its names lead to, under ``root``."""

    def __init__(self, root: str):
        self.root = root
        self.bench = _load_json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, cell: dict) -> dict:
        entry = next(c for c in self.bench["configs"] if c["name"] == cell["config"])
        return _load_json(os.path.join(self.root, entry["file"]))

    def kind(self, cell: dict):
        """The module of the kind of the cell's configuration."""
        return self.kind_of(self.config(cell))

    def kind_of(self, config: dict):
        """The module of a configuration's kind; one that names none is a fleet."""
        name = config.get("kind", "fleet")
        return _load_module(os.path.join(self.root, "bench", "kinds", name + ".py"),
                            f"bench_kind_{name}")

    def traffic(self, cell: dict) -> dict:
        return _load_json(os.path.join(self.root, "bench", "traffic",
                                       cell["traffic"] + ".json"))

    def driver(self, traffic: dict):
        name = traffic["driver"]
        return _load_module(os.path.join(self.root, "bench", "drivers", name + ".py"),
                            f"bench_driver_{name}")

    def metrics(self, cell: str, trace: bool) -> List[dict]:
        """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
        return [m for m in self.bench["per_layer" if trace else "end_to_end"]
                if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        return _load_module(os.path.join(self.root, "bench", "metrics", metric + ".py"),
                            f"bench_metric_{metric}")

    def planter(self, name: str):
        """The module that plants faults in the timed program ``name``."""
        return _load_module(os.path.join(self.root, "bench", "faults", name + ".py"),
                            f"bench_fault_{name}")


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    setup_s: float                 # process start to the first timed call
    compile_s: float               # seconds compiling (or loading) during set-up
    compiles_in_window: int        # programs compiled or loaded in the window
    calls: List[tuple]             # (start, end) of each call, host clock
    row_hours: int                 # rows x hours decided in the window
    window_s: float                # first call's start to last call's end
    trace: Optional[tracing.TraceSummary] = None


def _devices(chips: int, require_accelerator: bool):
    import jax

    devs = jax.devices()
    if require_accelerator and devs[0].platform == "cpu":
        raise NoAccelerator("no accelerator: JAX found only the CPU")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def run_cell(registry: Registry, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, program=None,
             require_accelerator: bool = True, compile_cache: bool = True) -> Dict:
    """One run; returns the result line as a dict, ``checks`` last."""
    cell = registry.cell(workload)
    config, traffic = registry.config(cell), registry.traffic(cell)
    driver_mod = registry.driver(traffic)
    kind = registry.kind(cell)
    wanted = registry.metrics(workload, trace)
    devs = _devices(int(cell["chips"]), require_accelerator)
    import jax

    if program is None:
        program = kind.SUT
    if compile_cache:
        use_compile_cache(registry.root)
    log = CompileLog()

    span = jax.profiler.TraceAnnotation if trace else (lambda name: contextlib.nullcontext())
    built = kind.build(config, seed)
    drv = driver_mod.Driver(built, config, traffic, seed, span, program)
    drv.setup()
    compile_s, n_setup, hits = log.snapshot()
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)

    # The caller files each call's outputs between calls, inside the window
    # but outside the call's latency; ``keep_s`` says what that costs.
    calls, row_hours, keep_s = [], 0, 0.0
    w0 = time.perf_counter()
    setup_s = w0 - t_start
    while not calls or calls[-1][1] - w0 < seconds:
        t0 = time.perf_counter()
        with span(tracing.CALL_SPAN):
            row_hours += drv.call()
        t1 = time.perf_counter()
        calls.append((t0, t1))
        drv.keep()
        keep_s += time.perf_counter() - t1
    if trace:
        jax.profiler.stop_trace()
    compiles_in_window = log.snapshot()[1] - n_setup
    used = devs[:int(cell["chips"])]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    drv.finish()
    gc.collect()

    run = Run(setup_s=setup_s, compile_s=compile_s, compiles_in_window=compiles_in_window,
              calls=calls, row_hours=row_hours, window_s=calls[-1][1] - w0)
    if trace:
        try:
            events = tracing.load(trace_dir)
            print(f"bench: trace events per device line {events.lines}", file=sys.stderr)
            run.trace = tracing.reduce(events)
            if run.trace is None:
                raise RuntimeError("the trace holds no call span or no device operation:\n"
                                   + "\n".join(tracing.describe(trace_dir)))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    t_ref = time.perf_counter()
    ref = kind.reference(built, drv.hours_needed())
    checks, bad_calls = drv.checks(ref)
    print(f"bench: {workload} seed={seed} setup_s={setup_s:.3f} compiles={n_setup} "
          f"cache_hits={hits} calls={len(calls)} window_s={run.window_s:.3f} keep_s={keep_s:.4f} "
          f"reference_s={time.perf_counter() - t_ref:.3f}", file=sys.stderr)
    correct = all(c.ok for c in checks)

    metrics = {}
    for m in wanted:
        value = registry.reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(calls),
              "failed": len(bad_calls) if bad_calls else (0 if correct else len(calls)),
              "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
        result["breakdown"] = {"device_ops": run.trace.device_ops,
                               "idle_gaps": run.trace.idle_gaps}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    return result


def report_checks(result: Dict, out=sys.stderr) -> None:
    """Each number compared beside its limit, as the last lines of stderr."""
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=out)
    print(f"correct = {str(result['correct']).lower()}", file=out, flush=True)
