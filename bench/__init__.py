"""The chip benchmark: one cell of BENCHMARK.json, run once.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the root of a checkout. Everything that belongs to
one configuration, traffic mix or per-layer metric lives in a file of
its own and is found by name (``bench/harness.py``).
"""
