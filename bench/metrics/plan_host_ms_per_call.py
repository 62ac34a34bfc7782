"""Host time inside ``plan_fleet`` per call: spec stacking, policy resolution
and the jitted call's enqueue (the program's ``fleet.plan`` span), in ms.
The caller's fetch of the outputs lies outside it."""
from bench.program_spans import span_ms_per_call


def read(run):
    return span_ms_per_call(run, "fleet.plan")
