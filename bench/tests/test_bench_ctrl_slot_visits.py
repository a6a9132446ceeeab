"""The reader of the armed controller step's slot visits per round."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
from bench import harness  # noqa: E402
from repro import obs  # noqa: E402

NAME = "ctrl_slot_visits_per_round"


def _run_record():
    return harness.RunRecord(window_wall=30.0, host_wall=24.0, super_ticks=9,
                             ticks=9, compiles=0, ctrl_s=None,
                             ticks_to_finish=None, convergence_tick=None,
                             trace=None)


def _reader():
    return harness.load_readers(harness.load_spec(),
                                "w3-sort.price-skew")[NAME]


def test_the_ratio_of_visits_to_rounds(monkeypatch):
    monkeypatch.setattr(obs, "counters", lambda: {
        "engine.super_ticks": 10, "ctrl.rounds": 400,
        "ctrl.slot_visits": 3630})
    assert _reader()(_run_record()) == pytest.approx(9.075)


@pytest.mark.parametrize("counters", [
    {}, {"engine.super_ticks": 10, "device.readbacks": 35},
    {"ctrl.rounds": 0, "ctrl.slot_visits": 0}])
def test_nothing_without_rounds_in_the_step(monkeypatch, counters):
    monkeypatch.setattr(obs, "counters", lambda: dict(counters))
    assert _reader()(_run_record()) is None


def test_only_the_armed_cell_reports_it():
    spec = harness.load_spec()
    assert NAME in harness.load_readers(spec, "w3-sort.price-skew")
    assert NAME not in harness.load_readers(spec, "w1-join.ca-hot")
