"""Readings that set a cell's limits: the program on many seeds, a
lower-precision control on a few, in one process on the chip.

    python3 bench/readings.py --workload <cell> --seeds 1,2,...
        [--control kernel|float32-values --control-seeds 7,8,9]

Each seed runs one execution of the timed path (a fresh graph driven by
``Engine.run()`` to its end) and compares it with the plain reference, as
a benchmark run does.  One JSON line per execution, then a summary: for
each compared number, the largest reading of the program (the lower
reading of its limit) and the smallest of the control (the upper one).
The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control", choices=("kernel", "float32-values"))
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("readings: needs a TPU", file=sys.stderr)
        return 2
    from bench import harness
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    runs = [(s, None) for s in args.seeds]
    runs += [(s, args.control) for s in args.control_seeds]
    lower, upper = {}, {}
    for seed, control in runs:
        cell = harness.Cell(args.workload, seed, control=control)
        t = harness.now()
        numbers = harness.readings(cell)
        print(json.dumps(dict(seed=seed, control=control, numbers=numbers,
                              seconds=harness.now() - t)), flush=True)
        side = upper if control else lower
        for k, v in numbers.items():
            side[k] = (max if side is lower else min)(side.get(k, v), v)
    print(json.dumps(dict(workload=args.workload, lower=lower, upper=upper,
                          limits=cell.limits)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
