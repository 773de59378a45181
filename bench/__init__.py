"""Chip benchmark of the speculative serving path.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the accelerator and prints one
JSON result line.  Everything that belongs to one configuration, traffic mix
or per-layer metric is a file found by its name (``spec.py``).
"""
