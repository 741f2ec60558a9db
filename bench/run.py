#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell needs, or when the program's ``src/`` is not in the checkout. See
``harness.py`` for what a run does.
"""

import sys
import time

T_START = time.perf_counter()

import harness  # noqa: E402

if __name__ == "__main__":
    harness._T_START = T_START
    sys.exit(harness.main())
