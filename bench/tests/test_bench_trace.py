"""``bench/trace.py`` on a small synthetic trace with known answers."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)
from bench import trace  # noqa: E402

# Two chips over the stretch [0, 10] s.  Chip 0 is busy in [1, 3] (two
# overlapping ops) and [6, 7]; chip 1 in [0, 2] and an op that reaches past
# the stretch, [9, 12].
DEVICES = {
    "/device:TPU:0": [(1.0, 2.5, "fusion.1"), (2.0, 3.0, "scatter.2"),
                      (6.0, 7.0, "fusion.1")],
    "/device:TPU:1": [(0.0, 2.0, "fusion.1"), (9.0, 12.0, "sort.3")],
}
# Host spans on the harness's thread: a super-tick [0, 8] holding a
# controller entry [3, 5] that holds a dispatch [4, 4.5]; then a snapshot
# [8.5, 9.5].
HOST = [(0.0, 8.0, "engine.super_tick"), (3.0, 5.0, "ctrl.step"),
        (4.0, 4.5, "PjitFunction(step)"), (8.5, 9.5, "sink.snapshot")]


def test_union_and_gaps():
    cover = trace.union([(2.0, 3.0), (1.0, 2.5), (6.0, 7.0)])
    assert cover == [(1.0, 3.0), (6.0, 7.0)]
    assert trace.gaps(cover, 0.0, 10.0) == [(0.0, 1.0), (3.0, 6.0),
                                           (7.0, 10.0)]


def test_timeline_names_the_innermost_open_span():
    assert trace.timeline(HOST) == [
        (0.0, 3.0, "engine.super_tick"), (3.0, 4.0, "ctrl.step"),
        (4.0, 4.5, "PjitFunction(step)"), (4.5, 5.0, "ctrl.step"),
        (5.0, 8.0, "engine.super_tick"), (8.5, 9.5, "sink.snapshot")]


def test_reduce_busy_idle_ops_and_gap_attribution():
    r = trace.reduce(DEVICES, HOST, (0.0, 10.0))
    # busy: chip 0 3 s, chip 1 2 s + 1 s clipped at the stretch's end
    assert r["chips"] == 2 and r["window_s"] == 10.0
    assert r["busy_s"] == pytest.approx(3.0)
    assert r["idle_share"] == pytest.approx(0.7)
    assert r["op_events"] == pytest.approx((3 + 2) / 2)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx((1.5 + 1.0 + 2.0) / 2)
    assert ops["scatter.2"] == pytest.approx(0.5)
    assert ops["sort.3"] == pytest.approx(0.5)
    # chip 0 idle [0,1] [3,6] [7,10]; chip 1 idle [2,9]
    idle = dict(r["idle_gaps"])
    assert idle["engine.super_tick"] == pytest.approx((1 + 1 + 1) / 2
                                                      + (1 + 3) / 2)
    assert idle["ctrl.step"] == pytest.approx((1.5 + 1.5) / 2)
    assert idle["PjitFunction(step)"] == pytest.approx((0.5 + 0.5) / 2)
    assert idle["sink.snapshot"] == pytest.approx((1.0 + 0.5) / 2)
    assert idle[trace.NO_SPAN] == pytest.approx((1.0 + 0.5) / 2)
    assert sum(idle.values()) == pytest.approx(10.0 - r["busy_s"])
    assert [name for name, _ in r["idle_gaps"]][0] == "engine.super_tick"


def test_a_trace_with_no_device_plane_reads_nothing(tmp_path):
    assert trace.reduce_file(str(tmp_path), {"/device:TPU:0"}) is None
