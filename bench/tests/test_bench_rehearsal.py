"""The benchmark's harness at tiny sizes on the CPU jit plane.

Each cell runs end to end as on the chip (set-up, window, finishing run,
check) with the chip check skipped: the result line carries the
contract's keys and every metric of the cell, a window cut at a
super-tick boundary and finished by ``Engine.run()`` gives the output of
an uninterrupted run, and the command line refuses without a TPU.
"""
import contextlib
import json
import os
import sys

import numpy as np
import pytest

pytest.importorskip("jax")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
from bench import harness  # noqa: E402

#: each cell cut to a size the CPU runs in seconds.
TINY = {
    "w1-join.ca-hot": {"config": {"scale": 0.012, "num_workers": 7}},
    "w3-sort.price-skew": {"config": {
        "scale_factor": 0.0015, "num_workers": 5, "num_ranges": 10,
        "batch_ticks": 16, "snapshot_every": 16}},
}
#: per-layer metrics read from a device trace, which the CPU has not.
DEVICE_ONLY = {"device_idle_share"}
SEED = 2**31 + 11          # larger than 32 signed bits hold


def _cell_metrics(spec, kind, cell):
    return {m["name"] for m in spec[kind] if harness._applies(m, cell)}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", list(TINY))
def test_result_line_has_the_contract_keys_and_the_cells_metrics(cell, trace):
    spec = harness.load_spec()
    result = harness.run(cell, SEED, 0.3, bool(trace), executor="jit",
                         overrides=TINY[cell])
    assert result["correct"] is True, result["checks"]
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(result)
    assert list(result)[-1] == "checks"
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(result["device"])
    kind = "per_layer" if trace else "end_to_end"
    want = _cell_metrics(spec, kind, cell) - (DEVICE_ONLY if trace else set())
    assert set(result["metrics"]) == want
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] is not None
    if trace:
        assert result["metrics"]["window_compiles"]["value"] == 0
    else:
        assert all(m["value"] > 0 for m in result["metrics"].values())
    json.dumps(result)


@pytest.mark.parametrize("cell", list(TINY))
def test_window_cut_at_a_boundary_then_finished_matches_an_uninterrupted_run(
        cell):
    c = harness.Cell(cell, SEED, executor="jit", overrides=TINY[cell])
    whole = c.build()
    whole[0].run(c.max_ticks)
    ex = harness.Execution(*c.build(), start=harness.now())
    window = harness._Window(float("inf"), lambda name: _null())
    window.attach(ex)
    cut_after = 3
    window.on_boundary = lambda wall: (
        setattr(window, "deadline", -1.0) if len(ex.steps) >= cut_after
        else None)
    with pytest.raises(harness._WindowClosed):
        ex.engine.run(c.max_ticks)
    assert len(ex.steps) == cut_after and not ex.engine.done()
    window.deadline = None
    ex.engine.run(c.max_ticks)
    a = c.mod.outputs(*whole)
    b = c.mod.outputs(ex.engine, ex.last_op, ex.sink)
    assert ex.engine.tick == whole[0].tick
    assert len(a["series"]) == len(b["series"])
    for (ta, ca), (tb, cb) in zip(a["series"], b["series"]):
        assert ta == tb and np.array_equal(ca, cb)
    for key in set(a) - {"series"}:
        assert np.array_equal(a[key], b[key]), key
    assert not c.failed(c.numbers(c.record(ex, 0)))


def _null():
    return contextlib.nullcontext()


def test_command_line_refuses_without_a_tpu(capsys):
    run = harness._load_module(os.path.join(ROOT, "bench", "run.py"),
                               "bench_run_cli")
    rc = run.main(["--workload", "w1-join.ca-hot", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""
