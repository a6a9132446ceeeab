"""The DSB store_sales group-by cell through the harness, tiny, on the CPU
jit plane: the run is correct against the plain reference, its result
line carries the cell's metrics, and the program's counters see the
group-by's padded fold lanes and the dispatches that route with split
keys."""
import os
import sys

import pytest

pytest.importorskip("jax")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
from bench import harness  # noqa: E402

CELL = "dsb-groupby.item-zipf2"
#: 5,761 rows over all 18,000 items and 8 workers: enough skew that
#: dispatches route with more split keys than ONEHOT_MAX_KEYS.
TINY = {"config": {"scale_factor": 0.002, "num_workers": 8,
                   "service_rate": 8, "filter_rate": 64, "batch_ticks": 8,
                   "snapshot_every": 8}}
SEED = 2**31 + 11          # larger than 32 signed bits hold


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_correct_with_its_metrics(trace):
    from repro import obs
    spec = harness.load_spec()
    before = obs.counters()
    result = harness.run(CELL, SEED, 0.3, bool(trace), executor="jit",
                         overrides=TINY)
    grew = {k: v - before.get(k, 0) for k, v in obs.counters().items()}
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in spec[kind] if harness._applies(m, CELL)}
    assert set(result["metrics"]) == want
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert want == ({"fold_lane_fill_pct"} if trace
                    else {"tuples_per_s", "setup_s"})
    # what this run's dispatches counted (the readers read the process's
    # totals, which other tests in this process add to)
    cfg = harness.Cell(CELL, SEED, overrides=TINY).cfg
    window = cfg["num_workers"] * cfg["filter_rate"] * cfg["batch_ticks"]
    n = grew["device.split_dispatches"]
    assert n > 0
    assert 2048 < grew["device.split_keys"] / n <= cfg["num_items"]
    # the rank pass of such a dispatch covers its whole padded chunk
    assert 256 * n <= grew["device.rank_lanes"] <= 2 * window * n
    assert 0 < grew["device.fold_live"] < grew["device.fold_lanes"]
