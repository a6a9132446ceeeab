"""Percent of the device stretch in which no operation ran on the chip
(traced with the host tracer off, so the host keeps its untraced pace)."""


def read(run):
    if run.trace is None or run.trace["idle_share"] is None:
        return None
    return 100.0 * run.trace["idle_share"]
