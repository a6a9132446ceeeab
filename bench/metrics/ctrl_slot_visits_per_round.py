"""Slots the armed controller step visits per metric round: the program's
counters ``ctrl.slot_visits`` (mitigation slots advanced plus skewed
workers assigned, summed over the step's rounds) over ``ctrl.rounds``.  A
step that visited every slot would read 2 x the workers.  The counters are
the process's totals, so the ratio covers every execution of the run
(set-up, the window, the finishing of the cut one); None where the program
keeps no such counters or ran no round in the step."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.counters()
    rounds = c.get("ctrl.rounds", 0)
    return c.get("ctrl.slot_visits", 0) / rounds if rounds else None
