"""Arrays copied from the device per call (the program's
``fleet.step.d2h_arrays`` counter): one blocking copy each."""
from bench.program_spans import count_per_call


def read(run):
    return count_per_call(run, "fleet.step.d2h_arrays")
