"""95th-percentile call latency over every call of the window, in
milliseconds; drain, wrap and renewal calls count like any other."""
import numpy as np


def read(run):
    if not run.calls:
        return None
    return float(np.percentile([e - s for s, e in run.calls], 95)) * 1e3
