"""Host time packing a call's input, per call: the ring gathers and the flat
host-to-device block (the program's ``fleet.step.pack`` span), in ms."""
from bench.program_spans import span_ms_per_call


def read(run):
    return span_ms_per_call(run, "fleet.step.pack")
