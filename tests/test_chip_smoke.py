"""``chip_smoke.py``'s phases at tiny sizes on the CPU jit plane.

Each phase carries its own checks (every edge on the jit plane, no
demotion / fallback / mismatch / sanitizer incident, integer results
equal to the numpy plane); a phase that returns passed them.  On the chip
the same functions run at full size from ``main``.
"""
import os
import sys

import pytest

pytest.importorskip("jax")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import chip_smoke  # noqa: E402

TINY = {
    "A": (chip_smoke.phase_w1, dict(scale=0.01, num_workers=8)),
    "B": (chip_smoke.phase_w3, dict(n_tuples=3_000, num_workers=6,
                                    batch_ticks=16, snapshot_every=16)),
    "B'": (chip_smoke.phase_w3, dict(n_tuples=3_000, num_workers=6,
                                     batch_ticks=16, snapshot_every=16,
                                     use_kernel=True)),
    "C": (chip_smoke.phase_chain, dict(n_rows=5_000, num_workers=6,
                                       service_rate=16, batch_ticks=8,
                                       snapshot_every=8)),
}


@pytest.mark.parametrize("phase", list(TINY))
def test_phase_passes_its_checks(phase, monkeypatch):
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    fn, kw = TINY[phase]
    rep = fn(executor="jit", **kw)
    assert rep["super_ticks"] > 0 and rep["ticks"] >= rep["super_ticks"]
    assert rep["events"] > 0                 # the controller acted


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main() != 0
    assert capsys.readouterr().out == ""     # no device record printed
