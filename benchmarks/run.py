"""Benchmark harness: one module per paper table/figure (§7.2-§7.12).

``python -m benchmarks.run [--only name]`` runs them all and prints
``bench,<columns...>`` CSV lines; each bench also persists its table to
results/bench/<name>.csv. The engine-throughput bench additionally writes
``BENCH_engine_throughput.json`` at the repo root (schema: mode / workers
/ chunk / tuples_per_sec + provenance: git_sha / jax_backend / timestamp)
so future PRs can diff the perf trajectory.

``--smoke`` runs every registered bench at a tiny size (scale/n_tuples
shrunk via signature introspection; internal size tables shrunk via
``common.smoke``) and *asserts* that each bench completes and emits a
non-empty, parseable table — the CI guard against bench bit-rot (wired
into tier-1 as ``tests/test_bench_smoke.py``).  Smoke numbers are
meaningless; they land in ``<name>.smoke.csv`` side paths (and a
``.smoke.json`` for the perf JSON), so a smoke run can never clobber
the committed result tables.  Real tables carry provenance columns
(git_sha / jax_backend / timestamp) plus the effective sizes, stamped
by ``common.emit``.

The roofline table (§Roofline) is produced by
``python -m benchmarks.roofline`` from the dry-run artifacts.
"""
from __future__ import annotations

import argparse
import csv
import inspect
import os
import sys
import time
import traceback

BENCHES = [
    ("engine_throughput", "bench_engine_throughput",
     "exchange data plane: tuples/sec, reference vs numpy vs pallas"),
    ("user_results", "bench_user_results", "§7.2 Fig16/17 result ratios"),
    ("first_phase", "bench_first_phase", "§7.3 Fig18/19 first phase"),
    ("heavy_hitter", "bench_heavy_hitter", "§7.4 Fig20 heavy hitters"),
    ("control_latency", "bench_control_latency", "§7.5 Fig21 ctrl latency"),
    ("dynamic_tau", "bench_dynamic_tau", "§7.6 Fig22 dynamic tau"),
    ("skew_levels", "bench_skew_levels", "§7.7 Fig23 skew levels"),
    ("distribution_change", "bench_distribution_change", "§7.8 Fig24"),
    ("metric_overhead", "bench_metric_overhead", "§7.9 Fig25 overhead"),
    ("sort", "bench_sort", "§7.10 Table2 sort"),
    ("multi_helpers", "bench_multi_helpers", "§7.11 Fig26 multi-helper"),
    ("moe_balance", "bench_moe_balance", "§7.12 second engine (MoE)"),
    ("recovery", "bench_recovery",
     "resilience: cut cost full vs incremental, recovery latency, chaos"),
    ("spill", "bench_spill",
     "out-of-core spill tier: throughput vs watermark, prefetch hit "
     "rate, pressure-mitigation latency"),
    ("roofline", "roofline", "§Roofline table from the dry-run artifacts"),
]

#: smoke-mode overrides applied by parameter name (signature-introspected).
SMOKE_KWARGS = {"scale": 0.02, "n_tuples": 2_000}

#: benches whose real inputs may be absent (dry-run artifacts): in smoke
#: mode they must *run* and emit a table, but the table may be empty.
SMOKE_MAY_BE_EMPTY = {"roofline"}


def _smoke_check(name: str) -> str:
    """Assert the bench's persisted table exists and parses; '' if ok."""
    from . import common
    path = os.path.join(common.RESULTS_DIR, f"{name}.smoke.csv")
    if not os.path.exists(path):
        return f"{name}: no table at {path}"
    with open(path, newline="") as f:
        rows = list(csv.DictReader(f))
    if not rows and name not in SMOKE_MAY_BE_EMPTY:
        return f"{name}: table is empty"
    if name == "engine_throughput":
        import json
        jpath = os.path.join(common.RESULTS_DIR,
                             "BENCH_engine_throughput.smoke.json")
        with open(jpath) as f:
            data = json.load(f)
        need = {"mode", "workers", "chunk", "tuples_per_sec", "plane",
                "git_sha", "jax_backend", "timestamp"}
        if not data or not all(need <= set(r) for r in data):
            return f"{name}: perf JSON rows missing fields {need}"
    if name == "recovery":
        idle = [r for r in rows if r["case"] == "cut-idle"]
        if {r["mode"] for r in idle} != {"full", "incremental"}:
            return f"{name}: missing full/incremental cut-idle rows"
        inc = next(r for r in idle if r["mode"] == "incremental")
        if int(inc["reused_ops"]) <= 0:
            return f"{name}: incremental idle cuts reused no sections"
        chaos = [r for r in rows if r["case"] == "chaos"]
        if not chaos or int(chaos[0]["identical"]) != 1:
            return f"{name}: chaos run not bit-identical"
    if name == "control_latency":
        # the mitigation-latency pair lands in its own table
        mpath = os.path.join(common.RESULTS_DIR,
                             "control_latency_mitigation.smoke.csv")
        if not os.path.exists(mpath):
            return f"{name}: no mitigation table at {mpath}"
        with open(mpath, newline="") as f:
            mrows = list(csv.DictReader(f))
        if not mrows or not {"batch_ticks", "plane",
                             "latency_ticks"} <= set(mrows[0]):
            return f"{name}: mitigation table empty or missing columns"
    return ""


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes; assert every bench runs + emits "
                         "valid tables (CI bit-rot guard)")
    args = ap.parse_args()
    if not args.smoke:
        # smoke runs tiny CPU compiles as a test; they leave no cache
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
        # Smoke doubles as the sanitizer leg: retrace sentinel, mirror
        # cross-checks and NaN guards armed for every bench.
        os.environ["REPRO_SANITIZE"] = "1"
        from . import common
        common.SMOKE = True
    failures = 0
    for name, module, desc in BENCHES:
        if args.only and args.only != name:
            continue
        print(f"# {name}: {desc}", flush=True)
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{module}", fromlist=["run"])
            if args.smoke:
                params = inspect.signature(mod.run).parameters
                kwargs = {k: v for k, v in SMOKE_KWARGS.items()
                          if k in params}
                mod.run(**kwargs)
                err = _smoke_check(name)
                if err:
                    raise AssertionError(err)
            else:
                mod.run()
            print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"# {name} FAILED", flush=True)
    if args.smoke:
        print(f"# smoke: {len(BENCHES)} benches, {failures} failures",
              flush=True)
    return failures


if __name__ == "__main__":
    sys.exit(main())
