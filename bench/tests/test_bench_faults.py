"""The check that decides ``correct`` sees broken timed paths.

Each cell runs end to end at a tiny size on the CPU jit plane, with the
chip check skipped, once for each fault the cell can have and once for
each lower-precision control; every run has to come out not correct.
The faults are planted in the device plane's sink fold, where the answer
is produced, both as a step of its own and as the tail of a fused chain:

* ``unchanged``: the fold returns its state unchanged;
* ``half``: half of each chunk's lanes are left out;
* ``altered``: one count is altered.

The controls: ``kernel``, the engine's own float32 Pallas fold, and
``float32-values``, the program fed the values rounded to float32.  The
cells run on one chip, so there is no exchange between chips to leave out.
"""
import os
import sys

import pytest

jax = pytest.importorskip("jax")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
from bench import harness  # noqa: E402

TINY = {
    "w1-join.ca-hot": {"config": {"scale": 0.012, "num_workers": 7}},
    "w3-sort.price-skew": {"config": {
        "scale_factor": 0.0015, "num_workers": 5, "num_ranges": 10,
        "batch_ticks": 16, "snapshot_every": 16}},
}
SEED = 2**31 + 23


def _copy(state):
    return jax.tree_util.tree_map(lambda a: a.copy(), state)


def _broken(fault):
    """The sink fold and the chain step with ``fault`` planted."""
    from repro.dataflow import device
    jnp = jax.numpy
    sink, chain = device._step_for("sink"), device._step_for("chain")

    def half(chunk):
        if chunk is None:
            return None
        keys, vals, valid = chunk
        return keys, vals, valid & (jnp.arange(valid.shape[0]) % 2 == 0)

    def spoil(before, after):
        if fault == "unchanged":
            return before
        if fault == "altered":
            return dict(after, counts=after["counts"].at[0].add(1))
        return after

    def sink_step(spec, consts, state, chunk):
        before = _copy(state)
        if fault == "half":
            chunk = half(chunk)
        state, out = sink(spec, consts, state, chunk)
        return spoil(before, state), out

    def chain_step(specs, consts_t, states_t, chunk, budgets):
        if specs[-1].kind != "sink":
            return chain(specs, consts_t, states_t, chunk, budgets)
        before = _copy(states_t[-1])
        if fault == "half":
            chunk = half(chunk)
        states, out, metrics = chain(specs, consts_t, states_t, chunk,
                                     budgets)
        return (states[:-1] + (spoil(before, states[-1]),), out, metrics)

    return sink_step, chain_step


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("cell", list(TINY))
def test_a_broken_sink_fold_is_not_correct(cell, fault, monkeypatch):
    from repro.dataflow import device
    sink_step, chain_step = _broken(fault)
    monkeypatch.setitem(device._STEP_CACHE, "sink", sink_step)
    monkeypatch.setitem(device._STEP_CACHE, "chain", chain_step)
    result = harness.run(cell, SEED, 0.3, False, executor="jit",
                         overrides=TINY[cell])
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 1
    assert result["checks"]["count_mismatch"]["value"] > 0


@pytest.mark.parametrize("control", ["kernel", "float32-values"])
@pytest.mark.parametrize("cell", list(TINY))
def test_a_float32_control_is_not_correct(cell, control):
    result = harness.run(cell, SEED, 0.3, False, executor="jit",
                         overrides=TINY[cell], control=control)
    assert result["correct"] is False
    checks = result["checks"]
    assert checks["count_mismatch"]["value"] == 0
    assert checks["sums_rel_err"]["value"] > checks["sums_rel_err"]["limit"]


def test_a_dropped_tuple_is_not_correct():
    cell = "w1-join.ca-hot"
    c = harness.Cell(cell, SEED, executor="jit", overrides=TINY[cell])
    c.prog = dict(c.prog, keys=c.prog["keys"][1:], vals=c.prog["vals"][1:])
    numbers = harness.readings(c)
    assert numbers["count_mismatch"] == 1 and c.failed(numbers)
