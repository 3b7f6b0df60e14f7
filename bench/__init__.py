"""On-chip benchmark of the ALEA profiler.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once. Every configuration, traffic mix,
traffic generator, per-layer metric and plain reference is a file of its
own under this directory, found by the name ``BENCHMARK.json`` (or the
traffic mix) gives it.
"""
