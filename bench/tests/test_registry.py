"""A configuration, a traffic mix, a metric and a kind added as new files,
with new ``BENCHMARK.json`` entries and no existing file edited, are found by
name."""
from __future__ import annotations

import filecmp
import json
import os
import time

import numpy as np

from bench import scenario
from bench.harness import Registry, run_cell
from bench.tests.conftest import REPO, SEED, run_tiny


def test_new_config_mix_and_metric_need_only_new_files(tiny_root):
    before = {os.path.relpath(os.path.join(d, f), tiny_root)
              for d, _, fs in os.walk(tiny_root) for f in fs}
    with open(os.path.join(tiny_root, "bench", "configs", "fleet2048.json")) as f:
        cfg = json.load(f)
    cfg.update(name="fleet12", n_links=12)
    with open(os.path.join(tiny_root, "bench", "configs", "fleet12.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(tiny_root, "bench", "traffic", "stream_k12.json"), "w") as f:
        json.dump({"driver": "stream", "hours_per_call": 12, "warm_hours": 337}, f)
    with open(os.path.join(tiny_root, "bench", "metrics", "calls_in_window.py"), "w") as f:
        f.write("def read(run):\n    return len(run.calls)\n")

    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "fleet12", "source": "test", "file": "bench/configs/fleet12.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "fleet12.stream_k12", "config": "fleet12",
                               "traffic": "stream_k12", "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "calls_in_window", "unit": "count", "better": "higher",
                                "bound": 0.25, "source": "host_clock",
                                "workloads": ["fleet12.stream_k12"]})
    with open(path, "w") as f:
        json.dump(bench, f)

    r = run_tiny(tiny_root, "fleet12.stream_k12", seconds=1.0)
    assert r["correct"], r["checks"]
    assert r["metrics"]["calls_in_window"]["value"] == r["attempted"]
    assert r["metrics"]["row_hours_per_s"]["value"] > 0
    # Every file that was there before is as it was in the repository.
    for rel in before - {"BENCHMARK.json"}:
        if not rel.startswith("bench/configs/"):
            assert filecmp.cmp(os.path.join(tiny_root, rel), os.path.join(REPO, rel), shallow=False), rel


STUB_KIND = '''
import numpy as np

from bench import control, reference as _reference, scenario, sut

SUT, CONTROL = sut, control
TINY = {"n_links": 8, "horizon": 480, "hours_per_month": 120}
FAULTS = ("plan_program",)


def build(config, seed):
    fleet = scenario.build(config, seed)
    order = np.random.default_rng(seed % 2**64).permutation(len(fleet.links))
    return scenario.Fleet(tuple(fleet.links[i] for i in order), fleet.demand[order],
                          fleet.hours_per_month)


def reference(built, hours):
    return _reference.run(scenario.LinkArrays(built.links, built.hours_per_month),
                          built.demand[:, :hours])
'''


class _Spy(Registry):
    """Counts the harness's calls of the kind it is handed."""

    def kind(self, cell):
        kind = super().kind(cell)
        self.used = {"file": kind.__file__, "build": 0, "reference": 0}
        build, reference = kind.build, kind.reference

        def counted_build(*a):
            self.used["build"] += 1
            return build(*a)

        def counted_reference(*a):
            self.used["reference"] += 1
            return reference(*a)

        kind.build, kind.reference = counted_build, counted_reference
        return kind


def test_new_kind_needs_only_new_files(tiny_root):
    before = {os.path.relpath(os.path.join(d, f), tiny_root)
              for d, _, fs in os.walk(tiny_root) for f in fs}
    stub = os.path.join(tiny_root, "bench", "kinds", "shuffled.py")
    with open(stub, "w") as f:
        f.write(STUB_KIND)
    with open(os.path.join(tiny_root, "bench", "configs", "fleet2048.json")) as f:
        cfg = json.load(f)
    cfg.update(name="shuffled8", kind="shuffled")
    with open(os.path.join(tiny_root, "bench", "configs", "shuffled8.json"), "w") as f:
        json.dump(cfg, f)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "shuffled8", "source": "test",
                             "file": "bench/configs/shuffled8.json", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "shuffled8.plan", "config": "shuffled8",
                               "traffic": "plan", "chips": 1, "why": "test"})
    with open(path, "w") as f:
        json.dump(bench, f)

    spy = _Spy(tiny_root)
    r = run_cell(spy, "shuffled8.plan", SEED, 0.5, False, t_start=time.perf_counter(),
                 require_accelerator=False, compile_cache=False)
    assert r["correct"], r["checks"]
    assert spy.used == {"file": stub, "build": 1, "reference": 1}
    registry = Registry(tiny_root)
    kind = registry.kind(registry.cell("shuffled8.plan"))
    rows, fleet_rows = kind.build(cfg, SEED).demand, scenario.build(cfg, SEED).demand
    assert not np.array_equal(rows, fleet_rows), "the stub kind built the fleet's own order"
    assert sorted(map(tuple, rows)) == sorted(map(tuple, fleet_rows))

    r = run_tiny(tiny_root, "shuffled8.plan", seconds=0.5, program=kind.CONTROL)
    assert not r["correct"], r["checks"]
    # Every file that was there before is as it was in the repository.
    for rel in before - {"BENCHMARK.json"}:
        if not rel.startswith("bench/configs/"):
            assert filecmp.cmp(os.path.join(tiny_root, rel), os.path.join(REPO, rel), shallow=False), rel


PLANTER = '''
PLANTED = []


def plant(monkeypatch, fault):
    PLANTED.append(fault)
'''


def test_a_cell_of_a_new_kind_in_the_real_benchmark_needs_only_new_files(tiny_root):
    """The guard: a stub kind, its planter, configuration and cell added as new
    files and new entries to a copy of the repository's own ``BENCHMARK.json``
    keep every kind invariant, get the per-layer metrics that name no cells,
    and find their planter by name."""
    from bench.tests.test_kinds import check_kinds

    before = {os.path.relpath(os.path.join(d, f), tiny_root)
              for d, _, fs in os.walk(tiny_root) for f in fs}
    with open(os.path.join(tiny_root, "bench", "kinds", "stubbed.py"), "w") as f:
        f.write(STUB_KIND.replace('FAULTS = ("plan_program",)', 'FAULTS = ("stub_program",)'))
    with open(os.path.join(tiny_root, "bench", "faults", "stub_program.py"), "w") as f:
        f.write(PLANTER)
    with open(os.path.join(tiny_root, "bench", "configs", "fleet2048.json")) as f:
        cfg = json.load(f)
    cfg.update(name="stubbed8", kind="stubbed")
    with open(os.path.join(tiny_root, "bench", "configs", "stubbed8.json"), "w") as f:
        json.dump(cfg, f)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        assert bench == json.load(f), "the copy is not the repository's own benchmark"
    bench["configs"].append({"name": "stubbed8", "source": "test",
                             "file": "bench/configs/stubbed8.json", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "stubbed8.plan", "config": "stubbed8",
                               "traffic": "plan", "chips": 1, "why": "test"})
    with open(path, "w") as f:
        json.dump(bench, f)

    registry = Registry(tiny_root)
    check_kinds(registry)
    kind = registry.kind(registry.cell("stubbed8.plan"))
    assert kind.__file__ == os.path.join(tiny_root, "bench", "kinds", "stubbed.py")
    traced = {m["name"] for m in registry.metrics("stubbed8.plan", trace=True)}
    assert {"device_idle_share", "device_ns_per_row_hour", "compiles_in_window",
            "compile_s"} <= traced
    planter = registry.planter(kind.FAULTS[0])
    assert planter.__file__ == os.path.join(tiny_root, "bench", "faults", "stub_program.py")
    planter.plant(None, "answer_altered")
    assert planter.PLANTED == ["answer_altered"]
    for rel in before - {"BENCHMARK.json"}:
        if not rel.startswith("bench/configs/"):
            assert filecmp.cmp(os.path.join(tiny_root, rel), os.path.join(REPO, rel), shallow=False), rel
