"""Chip benchmark: one cell of ``BENCHMARK.json`` per run, on a TPU.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell names a configuration file
(``benchmarks/chip/configs/``) and a traffic mix
(``benchmarks/chip/traffic/<traffic>.json``); per-layer metrics are read
by ``benchmarks/chip/metrics/<name>.py``.  All three are found by name,
so a new cell, mix or metric is new files and new entries only.

The last line of standard output is the result (one JSON object); the
numbers that decide ``correct`` are also printed, each beside its limit,
as the last lines of standard error.  Exits non-zero, printing no
result, where JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))

from chipbench.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], root=ROOT, t_start=T_START))
