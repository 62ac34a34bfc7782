"""Host time dispatching a call, per call: the host-to-device copy and the
jitted call's enqueue (the program's ``fleet.step.dispatch`` span), in ms."""
from bench.program_spans import span_ms_per_call


def read(run):
    return span_ms_per_call(run, "fleet.step.dispatch")
