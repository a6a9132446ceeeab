"""The engine's recorder (``repro.obs``) and the device plane's counted
readbacks, on the CPU jit plane."""
import numpy as np
import pytest

from repro import obs


def test_spans_nest_with_parent_indices_and_carry_their_args():
    with obs.recording() as rec:
        with obs.span("a"):
            with obs.span("b", site="x"):
                pass
            with obs.span("c"):
                with obs.span("d"):
                    pass
        with obs.span("e"):
            pass
    assert [(s.name, s.parent) for s in rec.spans] == [
        ("a", -1), ("b", 0), ("c", 0), ("d", 2), ("e", -1)]
    assert rec.spans[1].args == {"site": "x"}
    for s in rec.spans:
        assert s.start <= s.end
        if s.parent >= 0:
            p = rec.spans[s.parent]
            assert p.start <= s.start and s.end <= p.end
    assert rec.spans[0].end <= rec.spans[4].start


def test_off_path_is_one_shared_no_op_that_records_nothing():
    assert obs.span("x") is obs.span("y", site="z") is obs._NOOP
    with obs.span("x"):
        pass
    with obs.recording() as rec:
        pass
    assert rec.spans == []
    assert obs._active is None
    assert obs.span("x") is obs._NOOP        # off again after the recording


def test_counters_are_always_on_and_a_recording_keeps_their_growth():
    obs.count("test.obs.before", 5)
    with obs.recording() as rec:
        obs.count("test.obs.inside")
        obs.count("test.obs.inside", 2)
    obs.count("test.obs.after")
    assert rec.counters == {"test.obs.inside": 3}
    total = obs.counters()
    assert total["test.obs.before"] >= 5 and total["test.obs.after"] >= 1


def test_spanned_puts_a_whole_function_inside_a_span():
    @obs.spanned("f")
    def f(x, *, y):
        with obs.span("inner"):
            return x + y

    assert f.__name__ == "f"
    assert f(1, y=2) == 3                    # off: nothing recorded
    with obs.recording() as rec:
        assert f(2, y=3) == 5
    assert [(s.name, s.parent) for s in rec.spans] == [("f", -1),
                                                       ("inner", 0)]


def test_a_second_recording_inside_the_first_is_refused():
    with obs.recording():
        with pytest.raises(RuntimeError):
            with obs.recording():
                pass


def test_an_exception_closes_the_open_spans():
    with pytest.raises(ValueError):
        with obs.recording() as rec:
            with obs.span("outer"):
                raise ValueError
    assert [s.name for s in rec.spans] == ["outer"]
    assert obs._active is None


# --------------------------------------------------------------------- #
# The device plane                                                       #
# --------------------------------------------------------------------- #
jax = pytest.importorskip("jax")

from repro.dataflow import device as dev  # noqa: E402
from repro.dataflow.engine import Engine, Source  # noqa: E402
from repro.dataflow.operators import GroupByAgg, Sink  # noqa: E402


def test_readback_helper_counts_only_arrays_on_the_data_device(monkeypatch):
    host = np.arange(4)
    chip = jax.device_put(np.arange(4), dev._data_device())
    with obs.recording() as rec:
        assert np.array_equal(dev._readback(host, "t"), host)
        assert np.array_equal(dev._readback(chip, "t"), host)
        monkeypatch.setattr(dev, "_data_device", lambda: object())
        assert np.array_equal(dev._readback(chip, "t"), host)
    assert rec.counters == {"device.readbacks": 1}
    assert [(s.name, s.args) for s in rec.spans] == [
        ("device.readback", {"site": "t"})]


@pytest.mark.parametrize("kind", ["fold", "rows", "filter", "project",
                                  "probe", "sink", "chain", "ctrl"])
def test_each_jitted_step_is_named_by_its_kind(kind):
    assert dev._step_for(kind).__name__ == f"{kind}_step"


def _one_edge_groupby(num_workers=4, rate=100, batch_ticks=4):
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 16, 40 * rate).astype(np.int64)
    eng = Engine(partition_backend="pallas", device_executor="jit",
                 batch_ticks=batch_ticks)
    src = eng.add_source(Source("src", keys, rng.random(keys.size), rate))
    # each worker may pop the whole emission: the rings drain every tick
    grp = eng.add_op(GroupByAgg("grp", num_workers, rate))
    sink = eng.add_op(Sink("sink", 16, snapshot_every=0))
    eng.connect(src, grp, 16)
    eng.connect(grp, sink, 16)
    return eng, grp


def test_one_edge_groupby_pays_two_readbacks_per_dispatch():
    """A Source -> GroupBy -> Sink graph with no controller and no
    snapshot: each super-tick is one fold dispatch, which reads back its
    pushed and popped counts (``hist``, ``take``) and nothing else; the
    blocking GroupBy sends the sink nothing before END.  The rings drain
    every tick, so the records resident at the pops are the super-tick's
    emission; the fold ingests that emission in one padded chunk."""
    eng, grp = _one_edge_groupby()
    eng.run_super_tick(4)                    # allocates the device state
    rt = grp.device
    assert rt is not None and rt.kind == "fold"
    for _ in range(3):
        with obs.recording() as rec:
            eng.run_super_tick(4)
        assert rec.counters == {"engine.super_ticks": 1,
                                "device.readbacks": 2,
                                "device.ring_slots": rt.W * rt.cap,
                                "device.ring_live": 4 * 100,
                                "device.fold_lanes": rt.NB,
                                "device.fold_live": 4 * 100}
        assert [(s.name, s.args) for s in rec.spans] == [
            ("engine.super_tick", {}), ("device.dispatch", {}),
            ("device.readback", {"site": "hist"}),
            ("device.readback", {"site": "take"})]
        assert int(rt.lens.sum()) == 0


def test_trace_module_of_a_step_reads_jit_kind_step():
    eng, grp = _one_edge_groupby()
    eng.run_super_tick(4)
    rt = grp.device
    with dev._x64():
        text = dev._step_for("fold").lower(
            rt._spec(), rt.consts, rt.state, None, np.int64(0)).as_text()
    assert "jit_fold_step" in text
