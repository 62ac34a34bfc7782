"""Time spent copying a call's outputs from the device, per call: every
device-to-host copy (the program's ``fleet.step.fetch`` span), in ms."""
from bench.program_spans import span_ms_per_call


def read(run):
    return span_ms_per_call(run, "fleet.step.fetch")
