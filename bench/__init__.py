"""Chip benchmark of split serving: harness, traffic, references, readers.

Entry point: ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the repository root. Cells are listed in
``BENCHMARK.json``; everything a cell names (configuration, the reference
module the configuration names, traffic mix, limits, per-layer metric
reader, kernel operation counts) is a file under this directory, found by
its name.
"""
