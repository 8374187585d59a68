#!/usr/bin/env python3
"""The benchmark's command: one process, one cell, one run.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's files by the names in ``BENCHMARK.json``, sets the system
up and warms it (``setup_s``), measures for ``--seconds``, checks what the
timed path produced against the plain reference outside the window, and
prints the result object as the last line of its output. Earlier lines are
JSON objects too (phases of set-up, the numbers compared with their limits,
memory). Exits non-zero and prints no result without a TPU, with fewer chips
than the cell asks for, or on a ``device_kind`` that ``peaks.json`` lacks.
"""

import os
import sys
import time

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]  # the harness, then the program

if __name__ == "__main__":
    from harness.cell_run import main

    raise SystemExit(main(sys.argv[1:], t_process=T_PROCESS))
