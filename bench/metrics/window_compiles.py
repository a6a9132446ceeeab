"""Executables JAX built inside the window, compiled or loaded from the
persistent cache (there should be none: set-up warms every shape)."""


def read(run):
    return run.compiles
