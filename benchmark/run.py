#!/usr/bin/env python3
"""python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json on the machine this is started on.
The last line of standard output is the result; a run that finds no TPU (or
fewer chips than the cell asks for), or whose work did not go through the
device path, exits non-zero and prints none.
"""
import time

T_START = time.perf_counter()  # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))  # the program: lightgbm_tpu
sys.path.insert(0, HERE)                   # the benchmark's own modules

import harness  # noqa: E402

if __name__ == "__main__":
    harness.run(t_start=T_START)
