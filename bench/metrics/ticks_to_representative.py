"""Snapshot tick at which the window's first execution's visible ratio
settled within the paper's §7.2 bound (a count)."""


def read(run):
    return run.convergence_tick
