"""Device-to-host readbacks per engine super-tick: the program's counters
``device.readbacks`` over ``engine.super_ticks``.  The counters are the
process's totals, so the ratio covers every execution of the run (set-up,
the window, the finishing of the cut one); None where the program keeps
no such counters."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.counters()
    ticks = c.get("engine.super_ticks", 0)
    return c.get("device.readbacks", 0) / ticks if ticks else None
