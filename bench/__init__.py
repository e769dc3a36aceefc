"""Chip benchmark harness: one cell of ``BENCHMARK.json`` per run.

Run from the root of a checkout::

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Configurations (``configs/``), traffic mixes (``traffic/``), end-to-end
metrics (``e2e_metrics/``) and per-layer metrics (``layer_metrics/``) are
found by the names ``BENCHMARK.json`` gives them, one file each.
"""
