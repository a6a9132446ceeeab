"""Engine ticks per super-tick over the window (the scheduler's fusion)."""


def read(run):
    return run.ticks / run.super_ticks if run.super_ticks else None
