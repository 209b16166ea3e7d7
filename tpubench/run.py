#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 tpubench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the program is not in this checkout.
"""

import os
import sys
import time

T_START = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpubench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
