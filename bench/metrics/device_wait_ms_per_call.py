"""Time the host waits on the device program, per call (the program's
``fleet.step.wait`` span, ``block_until_ready`` on the outputs), in ms."""
from bench.program_spans import span_ms_per_call


def read(run):
    return span_ms_per_call(run, "fleet.step.wait")
