"""Live share of the lanes a map stage (Filter, Project) pops, in percent:
the program's counters ``device.map_pop_live`` (the records its pops
took) over ``device.map_pop_lanes`` (the flat windows they popped into).
The counters are the process's totals, so the share covers every execution
of the run (set-up, the window, the finishing of the cut one); None where
the program keeps no such counters or no map stage popped."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.counters()
    lanes = c.get("device.map_pop_lanes", 0)
    return 100.0 * c.get("device.map_pop_live", 0) / lanes if lanes else None
