"""Process start to the first timed call: import, scenario, device placement,
compile (or loading from the compile cache) and warm-up."""


def read(run):
    return run.setup_s
