"""Chip benchmark of coded training: one command runs one cell once.

    python3 -m perf.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric sits in a file of its own under this directory and is
found by the name ``BENCHMARK.json`` gives it (``perf/spec.py``).
"""
