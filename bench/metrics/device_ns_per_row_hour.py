"""Device-busy nanoseconds per row-hour decided in the traced window."""


def read(run):
    if run.trace is None or run.row_hours == 0:
        return None
    return run.trace.busy_s * 1e9 / run.row_hours
