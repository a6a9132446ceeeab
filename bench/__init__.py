"""Chip benchmark of the dataflow engine's device plane.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` (see ``run.py``).  Everything the
measurement depends on lives here, apart from the engine under test: the
data generators, the plain references and the comparison that decides
``correct``, the trace reduction and the table of peaks.
"""
