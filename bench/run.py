"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; everything
else is found by its name (see ``harness.py``).  The last line of standard
output is the result as one JSON object; the numbers compared with the
plain reference are the last lines of standard error.  The run refuses,
printing no result, unless JAX finds TPU chips, as many as the cell asks
for, and the chip's peaks are in ``peaks.json``.  The engine's ``REPRO_*``
settings are cleared: the benchmark runs the engine as a user does.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    spec = harness.load_spec(ROOT)
    cell = harness.find_cell(spec, args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU chip(s);"
              f" JAX found {len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 2
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if devices[0].device_kind not in peaks:
        print(f"bench: no peaks for {devices[0].device_kind!r} in peaks.json",
              file=sys.stderr)
        return 2

    for var in [v for v in os.environ if v.startswith("REPRO_")]:
        del os.environ[var]
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START, spec=spec)
    harness.print_result(result)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
