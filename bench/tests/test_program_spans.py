"""The readers of the program's own spans and counters, on hand-built events
and a hand-built ``Run`` (times in seconds on the host clock)."""
from __future__ import annotations

import collections

import pytest

from bench.harness import Registry, Run
from bench.tests.conftest import REPO

# Metric -> the span or counter it reads.
READS = {
    "host_pack_ms_per_call": "fleet.step.pack",
    "host_dispatch_ms_per_call": "fleet.step.dispatch",
    "device_wait_ms_per_call": "fleet.step.wait",
    "d2h_ms_per_call": "fleet.step.fetch",
    "host_mirror_ms_per_call": "fleet.step.mirror",
    "d2h_arrays_per_call": "fleet.step.d2h_arrays",
    "plan_host_ms_per_call": "fleet.plan",
}


def _run(calls):
    return Run(setup_s=1, compile_s=0, compiles_in_window=0, calls=calls,
               row_hours=0, window_s=calls[-1][1] - calls[0][0])


@pytest.fixture
def events(monkeypatch):
    """Replace the program's buffers with hand-built events: two calls in
    the window [10, 11.5], one before it (a warm-up) and one after it."""
    from repro.obs import profile

    spans, counts = [], []
    for t0 in (9.0, 10.0, 11.0, 11.9):
        for k, name in enumerate(READS.values()):
            if name == "fleet.step.d2h_arrays":
                counts.append((name, t0 + 0.05, 13))
            else:   # 1, 2, ... ms long, all inside the call
                spans.append((name, t0 + 0.01 * k, t0 + 0.01 * k + 1e-3 * (k + 1)))
    monkeypatch.setattr(profile, "_SPANS", collections.deque(spans))
    monkeypatch.setattr(profile, "_COUNTS", collections.deque(counts))
    return _run([(10.0, 10.5), (11.0, 11.5)])


@pytest.mark.parametrize("metric", sorted(READS))
def test_reader_takes_the_per_call_mean_inside_the_window(events, metric):
    """The calls at 9 s and 11.9 s lie outside the window [10, 11.5]. The
    two inside give the same value each, so the mean over two calls is one
    call's value."""
    k = list(READS.values()).index(READS[metric])
    value = Registry(REPO).reader(metric).read(events)
    expected = 13 if READS[metric].endswith("d2h_arrays") else float(k + 1)
    assert value == pytest.approx(expected)


@pytest.mark.parametrize("metric", sorted(READS))
def test_reader_divides_by_every_call_of_the_window(events, metric):
    """A third call in the window that recorded nothing still counts."""
    run = _run(events.calls + [(11.6, 11.7)])
    first = Registry(REPO).reader(metric).read(events)
    assert Registry(REPO).reader(metric).read(run) == pytest.approx(first * 2 / 3)


@pytest.mark.parametrize("metric", sorted(READS))
def test_reader_gives_none_where_nothing_was_recorded(monkeypatch, events, metric):
    reader = Registry(REPO).reader(metric)
    # Nothing of that name in the window: before the first recorded call.
    assert reader.read(_run([(1.0, 2.0)])) is None
    # A program without the recorder.
    from repro.obs import profile

    monkeypatch.delattr(profile, "recorded")
    assert reader.read(events) is None


def test_each_reader_is_in_the_benchmark_for_the_cells_that_record_it():
    bench = Registry(REPO).bench
    entries = {m["name"]: m for m in bench["per_layer"]}
    for metric, name in READS.items():
        m = entries[metric]
        plan = name.startswith("fleet.plan")
        assert m["workloads"] == (["fleet2048.plan"] if plan else
                                  ["fleet2048.stream_k24", "fleet2048.stream_k1"])
        assert m["source"] == ("program_counter" if name.endswith("d2h_arrays")
                               else "program_span")
