"""Percent of the ring slots a dispatch sweeps that hold records: the
program's counters ``device.ring_live`` over ``device.ring_slots`` (W x
ring capacity per ring-holding dispatch, chain stages included).  The
counters are the process's totals, so the share covers every execution of
the run; None where the program keeps no such counters."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.counters()
    slots = c.get("device.ring_slots", 0)
    return 100.0 * c.get("device.ring_live", 0) / slots if slots else None
