"""Seconds of tracing, lowering and compiling (or loading from the compile
cache) during set-up, from ``jax.monitoring``."""


def read(run):
    return run.compile_s
