"""Decision rows x hours delivered over the window, per second of it."""


def read(run):
    return run.row_hours / run.window_s
