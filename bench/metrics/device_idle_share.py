"""1 - (union of device-operation intervals) / traced window."""


def read(run):
    if run.trace is None:
        return None
    return 1.0 - run.trace.busy_s / run.trace.window_s
