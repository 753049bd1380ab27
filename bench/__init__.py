"""On-chip benchmark of the model search: one cell runs once per call of
``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``.

Cells, configurations, traffic mixes and per-layer metrics are data and
small files under this directory, found by the names in ``BENCHMARK.json``.
"""
