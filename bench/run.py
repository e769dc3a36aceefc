"""Benchmark entry point: run one cell of ``BENCHMARK.json`` on this
machine's chip and print its result as the last line of standard output.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, when JAX finds no TPU (or fewer chips
than the cell needs) and when run outside a checkout.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT

if __name__ == "__main__":
    from bench.harness import main
    sys.exit(main(t_start=T_START))
