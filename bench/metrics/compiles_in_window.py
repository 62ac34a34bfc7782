"""Programs compiled, or loaded from the compile cache, inside the window
(``jax.monitoring`` backend-compile events); 0 when set-up warmed every shape."""


def read(run):
    return run.compiles_in_window
