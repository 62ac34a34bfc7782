"""The reduction from a profiler trace to the per-layer metrics, on small
event lists whose answers are worked out by hand (times in ns)."""
from __future__ import annotations

import pytest

from bench import tracing
from bench.harness import Run

MS = 1_000_000


def _events():
    spans = [
        ("bench.call", 0, 10 * MS), ("bench.step", 0, 10 * MS),
        ("bench.call", 12 * MS, 20 * MS), ("bench.step", 12 * MS, 19 * MS),
        ("bench.reset", 19 * MS, 20 * MS),
    ]
    ops = [
        ("fusion.1", 2 * MS, 5 * MS), ("fusion.2", 4 * MS, 6 * MS),   # overlap: busy 2..6
        ("scan", 14 * MS, 16 * MS),
        ("late", 25 * MS, 30 * MS),                                      # outside the window
    ]
    return tracing.TraceEvents({"/device:TPU:0": ops}, spans)


def test_busy_union_and_idle_share():
    s = tracing.reduce(_events())
    assert s.window_s == pytest.approx(20e-3)
    assert s.busy_s == pytest.approx(6e-3)
    run = Run(setup_s=1, compile_s=0, compiles_in_window=0, calls=[(0, 0.01), (0.012, 0.02)],
              row_hours=48, window_s=0.02, trace=s)
    from bench.metrics import device_idle_share, device_ns_per_row_hour, exposed_host_ms_per_call
    assert device_idle_share.read(run) == pytest.approx(0.7)
    assert device_ns_per_row_hour.read(run) == pytest.approx(6e6 / 48)
    # Call 1: 10 ms span, 4 ms busy; call 2: 8 ms span, 2 ms busy.
    assert s.call_s == pytest.approx([10e-3, 8e-3])
    assert s.call_busy_s == pytest.approx([4e-3, 2e-3])
    assert exposed_host_ms_per_call.read(run) == pytest.approx(6.0)


def test_breakdown_ops_and_gaps_named_by_host_span():
    s = tracing.reduce(_events(), top=3)
    assert s.device_ops == [["fusion.1", pytest.approx(3e-3)], ["fusion.2", pytest.approx(2e-3)],
                            ["scan", pytest.approx(2e-3)]]
    # Idle inside the window, cut at span edges: 0-2, 6-10, 12-14 and 16-19 in
    # bench.step, 10-12 between the calls, 19-20 in bench.reset.
    assert s.idle_gaps == [["bench.step", pytest.approx(4e-3)], ["bench.step", pytest.approx(3e-3)],
                           ["bench.step", pytest.approx(2e-3)]]
    gaps = tracing.reduce(_events(), top=10).idle_gaps
    assert sorted(g[0] for g in gaps) == ["bench.reset", "bench.step", "bench.step", "bench.step",
                                          "bench.step", "outside"]
    assert sum(g[1] for g in gaps) == pytest.approx(14e-3)


def test_several_devices_average_and_empty_trace():
    ev = _events()
    ev.device["/device:TPU:1"] = [("x", 0, 20 * MS)]
    s = tracing.reduce(ev)
    assert s.busy_s == pytest.approx((6e-3 + 20e-3) / 2)
    assert tracing.reduce(tracing.TraceEvents({}, ev.spans)) is None
    assert tracing.reduce(tracing.TraceEvents(ev.device, [])) is None


def test_program_runs_keep_busy_whole_where_operations_are_missing():
    ev = _events()
    # The program of the second call ran 13-17 ms; the profiler kept only one
    # of its operations (14-16 ms).
    ev.programs["/device:TPU:0"] = [("jit_step", 13 * MS, 17 * MS)]
    s = tracing.reduce(ev)
    assert s.busy_s == pytest.approx(8e-3)
    assert s.call_busy_s == pytest.approx([4e-3, 4e-3])
    assert s.device_ops[-1] == ["scan", pytest.approx(2e-3)]   # the breakdown names operations


def test_readers_return_nothing_without_a_trace():
    run = Run(setup_s=1, compile_s=0, compiles_in_window=0, calls=[(0, 1)], row_hours=1,
              window_s=1)
    from bench.metrics import device_idle_share, device_ns_per_row_hour, exposed_host_ms_per_call
    for m in (device_idle_share, device_ns_per_row_hour, exposed_host_ms_per_call):
        assert m.read(run) is None


def test_load_reads_the_harness_spans_from_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with jax.profiler.TraceAnnotation(tracing.CALL_SPAN):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = tracing.load(str(tmp_path))
    names = sorted(n for n, _, _ in ev.spans)
    assert names == [tracing.CALL_SPAN] * 3 + ["bench.step"] * 3
    calls = sorted((s, e) for n, s, e in ev.spans if n == tracing.CALL_SPAN)
    steps = sorted((s, e) for n, s, e in ev.spans if n == "bench.step")
    assert all(c0 <= s0 and s1 <= c1 for (c0, c1), (s0, s1) in zip(calls, steps))
    assert all(p[1] <= q[0] for p, q in zip(calls, calls[1:]))
