"""Percent of the window's host wall spent inside the controller entries
(``ReshapeController.step`` host-stepped; ``DeviceController.super_tick``
and ``drain`` armed)."""


def read(run):
    if run.ctrl_s is None or run.host_wall <= 0:
        return None
    return 100.0 * run.ctrl_s / run.host_wall
