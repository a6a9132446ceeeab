"""Live share of the lanes a keyed fold ingests, in percent: the
program's counters ``device.fold_live`` (host-known live lanes) over
``device.fold_lanes`` (the padded lanes of each chunk a group-by's fold
dispatch ingests, and of the window a fused chain hands its group-by).
The counters are the process's totals, so the share covers every
execution of the run (set-up, the window, the finishing of the cut one);
None where the program keeps no such counters or folded nothing."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.counters()
    lanes = c.get("device.fold_lanes", 0)
    return 100.0 * c.get("device.fold_live", 0) / lanes if lanes else None
