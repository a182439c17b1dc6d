#!/usr/bin/env python3
"""``python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell of ``BENCHMARK.json``.  Earlier lines
are free text; the last line of standard output is the result object."""

import os
import sys
import time

T_START = time.perf_counter()          # set-up counts from the first line

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

if __name__ == "__main__":
    from benchmark import harness

    sys.exit(harness.main(root=ROOT, t_start=T_START))
