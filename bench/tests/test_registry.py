"""A configuration, a traffic mix and a metric added as new files, with new
``BENCHMARK.json`` entries and no existing file edited, are found by name."""
from __future__ import annotations

import filecmp
import json
import os

from bench.tests.conftest import REPO, run_tiny


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
