"""The readers of the program's counters, ``bench/spans.py``'s reductions
on synthetic spans and traces with known answers, and the recorder on tiny
cells of the CPU jit plane."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
from bench import harness, spans  # noqa: E402
from repro import obs  # noqa: E402
from repro.obs import Span  # noqa: E402

COUNTERS = {"engine.super_ticks": 10, "device.readbacks": 35,
            "device.ring_slots": 2000, "device.ring_live": 50}


def _run_record():
    return harness.RunRecord(window_wall=30.0, host_wall=24.0, super_ticks=9,
                             ticks=9, compiles=0, ctrl_s=None,
                             ticks_to_finish=None, convergence_tick=None,
                             trace=None)


def _reader(name):
    return harness.load_readers(harness.load_spec(), "w1-join.ca-hot")[name]


@pytest.mark.parametrize("name, value", [("readbacks_per_supertick", 3.5),
                                         ("ring_fill_pct", 2.5)])
def test_counter_readers_on_the_programs_totals(monkeypatch, name, value):
    read = _reader(name)
    monkeypatch.setattr(obs, "counters", lambda: dict(COUNTERS))
    assert read(_run_record()) == pytest.approx(value)
    monkeypatch.setattr(obs, "counters", lambda: {})
    assert read(_run_record()) is None


@pytest.mark.parametrize("name", ["readbacks_per_supertick",
                                  "ring_fill_pct"])
def test_counter_readers_read_nothing_from_a_program_without_them(
        monkeypatch, name):
    import repro
    read = _reader(name)
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert read(_run_record()) is None


# A recorded super-tick [0, 10] s: a controller entry [1, 5] holding a
# nested entry [2, 4] (counted once) and a CPU step [2.5, 3.5]; a dispatch
# [5, 6]; readbacks [6, 7] and [8, 8.5]; a state sync [7.5, 9] holding the
# second readback; END [9, 10].
SPANS = [Span("engine.super_tick", 0.0, 10.0, -1, {}),
         Span("ctrl.super_tick", 1.0, 5.0, 0, {}),
         Span("ctrl.drain", 2.0, 4.0, 1, {}),
         Span("ctrl.cpu_step", 2.5, 3.5, 2, {}),
         Span("device.dispatch", 5.0, 6.0, 0, {"kind": "fold"}),
         Span("device.readback", 6.0, 7.0, 0, {"site": "hist"}),
         Span("device.sync_host", 7.5, 9.0, 0, {}),
         Span("device.readback", 8.0, 8.5, 6, {"site": "sync_host"}),
         Span("engine.end", 9.0, 10.0, 0, {})]


def test_seconds_in_counts_nested_spans_of_the_same_set_once():
    assert spans.seconds_in(SPANS, spans.CTRL_ENTRIES) == 4.0
    assert spans.seconds_in(SPANS, ("device.readback",)) == 1.5
    assert spans.seconds_in(SPANS, ("device.sync_host",
                                    "device.readback")) == 2.5


def test_readings_of_a_recorded_stretch():
    r = spans.readings(SPANS, dict(COUNTERS, **{"engine.super_ticks": 1}),
                       wall=20.0)
    assert r["readbacks_per_supertick"] == 35
    assert r["readbacks_by_site"] == {"hist": 1, "sync_host": 1}
    assert r["ring_fill_pct"] == pytest.approx(2.5)
    assert r["readback_wait_share"] == pytest.approx(100 * 1.5 / 20)
    assert r["state_sync_share"] == pytest.approx(100 * 1.5 / 20)
    assert r["ctrl_span_share"] == pytest.approx(100 * 4 / 20)
    assert r["ctrl_cpu_step_share"] == pytest.approx(100 * 1 / 20)
    assert r["ctrl_replay_share"] == 0.0
    assert r["end_phase_s"] == 1.0
    assert r["supertick_span_ms"] == 10000.0


def test_phase_split_by_super_tick():
    two = SPANS + [Span("engine.super_tick", 10.0, 12.0, -1, {}),
                   Span("device.readback", 11.0, 11.5, 9, {"site": "take"})]
    out = spans.phase_split(two, 1)
    assert out["source"]["super_ticks"] == 1
    assert out["source"]["ms"]["device.readback"] == pytest.approx(1500.0)
    assert out["source"]["readback_ms_by_site"] == {"hist": 1000.0,
                                                    "sync_host": 500.0}
    assert out["drain"]["ms"] == {"engine.super_tick": 2000.0,
                                  "device.readback": 500.0}
    assert out["drain"]["readback_ms_by_site"] == {"take": 500.0}


#: perf_counter at the profiler session's start, and the trace clock's
#: offset from ``perf_counter - session``.
SESSION, OFFSET = 100.0, 0.25


def test_program_idle_on_device_intervals_shifted_by_a_known_offset():
    # the same super-tick recorded at perf_counter 100 + t; on the trace's
    # clock it reads t + OFFSET
    recorded = [s._replace(start=s.start + SESSION, end=s.end + SESSION)
                for s in SPANS]
    mapped = spans.on_trace_clock(recorded, SESSION, OFFSET)
    assert mapped[0] == (OFFSET, 10.0 + OFFSET, "engine.super_tick")
    # the chip is busy exactly inside the dispatch and END, on its clock
    devices = {"/device:TPU:0": [(5.0 + OFFSET, 6.0 + OFFSET, "fusion.1"),
                                 (9.0 + OFFSET, 10.0 + OFFSET, "fusion.2")]}
    r = spans.program_idle(devices, mapped, (OFFSET, 10.0 + OFFSET))
    assert r["stretch_s"] == pytest.approx(10.0)
    assert r["idle_s"] == pytest.approx(8.0)
    idle = dict(r["program_idle_gaps"])
    assert idle["device.readback"] == pytest.approx(1.5)
    assert idle["ctrl.cpu_step"] == pytest.approx(1.0)
    assert idle["ctrl.drain"] == pytest.approx(1.0)
    assert idle["ctrl.super_tick"] == pytest.approx(2.0)
    assert idle["device.sync_host"] == pytest.approx(1.0)
    assert idle["engine.super_tick"] == pytest.approx(1.5)
    assert sum(idle.values()) == pytest.approx(8.0)
    assert r["idle_in_readback_share"] == pytest.approx(100 * 1.5 / 8)
    # mapped without the offset, the spans slide against the chip's ops
    unshifted = spans.program_idle(
        devices, spans.on_trace_clock(recorded, SESSION),
        (OFFSET, 10.0 + OFFSET))
    assert dict(unshifted["program_idle_gaps"]) != idle
    assert spans.program_idle({}, mapped, (0.0, 1.0)) is None


def test_clock_error_pairs_each_span_with_the_nearest_event():
    recorded = [Span("engine.super_tick", SESSION + t, SESSION + t + 0.9,
                     -1, {}) for t in (1.0, 2.0, 3.0, 9.0)]
    events = [(t + 0.0004, t + 0.9, "engine.super_tick")
              for t in (1.0, 2.0, 3.0)] + [(0.5, 0.6, "ctrl.step")]
    err = spans.clock_error(spans.on_trace_clock(recorded, SESSION), events,
                            (0.0, 5.0))
    assert err["pairs"] == 3          # the span at 9 s lies past the stretch
    assert err["median_abs_dstart_s"] == pytest.approx(0.0004)
    assert spans.clock_error([], [], (0.0, 1.0)) is None


# --------------------------------------------------------------------- #
# The recorder inside tiny cells on the CPU jit plane                    #
# --------------------------------------------------------------------- #
TINY = {
    "w1-join.ca-hot": {"config": {"scale": 0.012, "num_workers": 7}},
    "w3-sort.price-skew": {"config": {
        "scale_factor": 0.0015, "num_workers": 5, "num_ranges": 10,
        "batch_ticks": 16, "snapshot_every": 16}},
}
#: spans each cell's executions must record.
EXPECTED = {
    "w1-join.ca-hot": {"engine.super_tick", "engine.end", "ctrl.step",
                       "device.dispatch", "device.readback",
                       "device.sync_host", "device.reload", "sink.snapshot"},
    "w3-sort.price-skew": {"engine.super_tick", "engine.end",
                           "ctrl.super_tick", "ctrl.cpu_step", "ctrl.drain",
                           "ctrl.replay", "ctrl.step", "device.dispatch",
                           "device.readback", "device.sync_host",
                           "sink.snapshot"},
}


@pytest.mark.parametrize("cell", list(TINY))
def test_every_span_nests_under_a_super_tick_and_results_are_unchanged(cell):
    c = harness.Cell(cell, 2**31 + 11, executor="jit", overrides=TINY[cell])
    off = c.build()
    off[0].run(c.max_ticks)
    on = c.build()
    with obs.recording() as rec:
        on[0].run(c.max_ticks)
    assert EXPECTED[cell] <= {s.name for s in rec.spans}
    for s in rec.spans:
        root = s
        while root.parent >= 0:
            root = rec.spans[root.parent]
        assert root.name == "engine.super_tick"
    assert rec.counters["engine.super_ticks"] == on[0].super_ticks
    assert rec.counters["device.readbacks"] == sum(
        s.name == "device.readback" for s in rec.spans)
    assert 0 < rec.counters["device.ring_live"] < rec.counters[
        "device.ring_slots"]
    a, b = c.mod.outputs(*off), c.mod.outputs(*on)
    assert len(a["series"]) == len(b["series"])
    for (ta, ca), (tb, cb) in zip(a["series"], b["series"]):
        assert ta == tb and np.array_equal(ca, cb)
    for key in set(a) - {"series"}:
        assert np.array_equal(a[key], b[key]), key


def test_the_script_rehearses_on_the_cpu_jit_plane(capsys):
    """``bench/spans.py`` end to end at a tiny size: whole executions with
    the recorder off and on, correct and with equal series, and a traced
    one whose host stretch puts the recorder's spans on the trace's clock
    (the CPU has no device plane to reduce)."""
    import json
    overrides = json.dumps(TINY["w1-join.ca-hot"])
    rc = spans.main(["--workload", "w1-join.ca-hot", "--seed", "2147483659",
                     "--pairs", "1", "--executor", "jit",
                     "--trace-seconds", "0", "--host-seconds", "0",
                     "--overrides", overrides])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert rc == 0
    off, on, summary = lines
    assert not off["recorded"] and on["recorded"]
    assert off["correct"] and on["correct"] and summary["series_equal"]
    assert on["readbacks_per_supertick"] > 0
    assert 0 < on["ctrl_span_share"] < 100
    traced = summary["traced"]
    assert "error" not in traced and traced["device"] is None
    assert traced["clock"]["pairs"] >= 1
    assert traced["clock"]["median_abs_dstart_s"] < 0.05
