#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell from ``--seed``, warms up, measures for ``--seconds``,
checks every answer against the plain reference and prints one JSON
result as the last line of standard output.  Exits non-zero, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t_start=T_START))
