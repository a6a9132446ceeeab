"""Host wall milliseconds per super-tick over the window (the profiler's
start and stop left out)."""


def read(run):
    return 1000.0 * run.host_wall / run.super_ticks if run.super_ticks else None
