"""Host time mirroring the float64 state and building the outputs, per call
(the program's ``fleet.step.mirror`` span), in ms."""
from bench.program_spans import span_ms_per_call


def read(run):
    return span_ms_per_call(run, "fleet.step.mirror")
