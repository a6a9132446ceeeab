"""The reader of the live share of the map stages' flat pop windows."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
from bench import harness  # noqa: E402
from repro import obs  # noqa: E402

NAME = "map_pop_fill_pct"


def _run_record():
    return harness.RunRecord(window_wall=30.0, host_wall=24.0, super_ticks=9,
                             ticks=9, compiles=0, ctrl_s=None,
                             ticks_to_finish=None, convergence_tick=None,
                             trace=None)


def _reader():
    return harness.load_readers(harness.load_spec(), "w1-join.ca-hot")[NAME]


def test_the_share_of_live_lanes(monkeypatch):
    monkeypatch.setattr(obs, "counters", lambda: {
        "device.map_pop_lanes": 4096, "device.map_pop_live": 1536,
        "device.fold_lanes": 10, "device.fold_live": 10})
    assert _reader()(_run_record()) == pytest.approx(37.5)


@pytest.mark.parametrize("counters", [
    {}, {"engine.super_ticks": 10, "device.fold_lanes": 512},
    {"device.map_pop_lanes": 0, "device.map_pop_live": 0}])
def test_nothing_where_no_map_stage_popped(monkeypatch, counters):
    monkeypatch.setattr(obs, "counters", lambda: dict(counters))
    assert _reader()(_run_record()) is None


def test_the_cells_that_report_it():
    spec = harness.load_spec()
    assert NAME in harness.load_readers(spec, "w1-join.ca-hot")
    assert NAME not in harness.load_readers(spec, "w3-sort.price-skew")


def test_a_w1_execution_counts_its_filter_pops():
    """The W1 cell, tiny, on the CPU jit plane: its Filter pops into flat
    windows and takes every tweet once."""
    pytest.importorskip("jax")
    c = harness.Cell("w1-join.ca-hot", 2**31 + 11, executor="jit",
                     overrides={"config": {"scale": 0.012,
                                           "num_workers": 7}})
    before = obs.counters()
    eng = c.build()[0]
    eng.run(c.max_ticks)
    grew = {k: v - before.get(k, 0) for k, v in obs.counters().items()}
    assert grew["device.map_pop_live"] == np.asarray(c.data["keys"]).size
    assert 0 < grew["device.map_pop_live"] < grew["device.map_pop_lanes"]
    assert 0 < _reader()(_run_record()) <= 100
