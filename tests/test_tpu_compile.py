"""Compile rehearsals for a described TPU v5e (no chip needed).

The TPU compiler is installed with JAX, so the main path's kernels and
jitted device steps can be compiled here for a chip that is described,
not attached: a Mosaic refusal, an unaligned block or a 64-bit index map
fails these tests the way it would fail on the chip.  Nothing runs, so
results and times are out of scope (``chip_smoke.py`` covers those on the
chip).  The topology is described inside a fixture, never at import: only
one process at a time may load the TPU library, and a test worker keeps
it until it exits.
"""
import importlib
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

kpart = importlib.import_module("repro.kernels.partition")

#: (K, W) of the paper's W1 (56 locations, 48 workers) and W3 (40 ranges,
#: 20 workers), with chunk lengths the exchange sees (a ragged tail
#: included).
WIDTHS = [(56, 48, 4_800), (56, 48, 600), (40, 20, 30_720)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_cache():
    """Compiles for a described chip cannot be read back from the
    persistent cache; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
@pytest.mark.parametrize("K,W,N", WIDTHS)
@pytest.mark.parametrize("name", ["partition", "partition_scatter",
                                  "partition_scatter_fold"])
def test_partition_kernel_compiles(name, K, W, N, x64, one_chip, no_cache):
    """Each partition kernel lowers through Mosaic (a ``tpu_custom_call``,
    not the interpreter), also inside the device plane's x64 scope."""
    fn = getattr(kpart, name)
    i32 = jax.ShapeDtypeStruct((N,), jnp.int32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((K, W), jnp.float32, sharding=one_chip)
    if name == "partition_scatter_fold":
        f32 = jax.ShapeDtypeStruct((N,), jnp.float32, sharding=one_chip)
        args = (i32, i32, f32, i32, table)

        def call(k, c, v, m, t):
            return fn(k, c, v, t, valid=m, cdf=t, interpret=False)
    else:
        args = (i32, i32, table)

        def call(k, c, t):
            return fn(k, c, t, cdf=t, interpret=False)
    with jax.enable_x64(x64):
        compiled = jax.jit(call).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def captured_steps():
    """StepSpecs and abstract arguments of every jitted device step a
    tiny CPU jit-plane run dispatches: W1 (filter -> probe chain, sink)
    and W3 (row store under an armed controller, which splits ranges)."""
    from repro.dataflow import build_w1, build_w3
    from repro.dataflow import device as dev
    real = dev._step_for
    seen = {}

    def spy(kind):
        step = real(kind)

        def call(spec, *args):
            shapes = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x),
                                               np.result_type(x)), args)
            seen.setdefault(kind, []).append((step, spec, shapes))
            return step(spec, *args)
        return call

    dev._step_for = spy
    try:
        build_w1(scale=0.005, num_workers=6, batch_ticks=4,
                 partition_backend="pallas", device_executor="jit").run()
        build_w3(n_tuples=3_000, num_workers=6, batch_ticks=16,
                 snapshot_every=16, partition_backend="pallas",
                 device_executor="jit", device_controller=True).run()
    finally:
        dev._step_for = real
    return seen


@pytest.mark.parametrize("kind", ["rows", "chain"])
def test_device_step_compiles(kind, captured_steps, one_chip, no_cache,
                              monkeypatch):
    """The jitted ``rows`` and ``chain`` steps, at the shapes a real run
    dispatched, compile for the chip under ``jax.enable_x64``.  (The
    ``ctrl`` step runs on the host's CPU backend.)"""
    monkeypatch.delenv("REPRO_SANITIZE", raising=False)  # lowering retraces
    calls = captured_steps.get(kind)
    if kind == "rows":             # the split-table ingest of a mitigation
        calls = [c for c in calls if c[1].any_split]
    assert calls, f"the CPU run dispatched no {kind!r} step"
    step, spec, shapes = calls[-1]
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    with jax.enable_x64(True):
        step.lower(spec, *args).compile()
