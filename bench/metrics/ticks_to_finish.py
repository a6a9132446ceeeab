"""Engine ticks the window's first execution took to finish (a count set
by the skew policy's decisions)."""


def read(run):
    return run.ticks_to_finish
