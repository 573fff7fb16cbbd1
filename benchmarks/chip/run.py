#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python3 benchmarks/chip/run.py --workload jacobi1024.steady \\
        --seed 7 --seconds 20 --trace 0

Run from the root of a checkout. The last line of standard output is
one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and ``checks`` last:
each number compared beside its limit, also the last lines of standard
error). Exits non-zero, printing no result, when JAX finds no TPU or
fewer chips than the cell asks for, and non-zero after printing when
the comparison fails.
"""

import time

T_START = time.perf_counter()   # set-up is counted from here

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
