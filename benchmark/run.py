#!/usr/bin/env python3
"""python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json on the TPU this process finds; the
last line of stdout is the result. See benchmark/harness.py and PERF.md."""

import time

T_START = time.perf_counter()   # setup_s counts from here

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))      # the program: spark_tpu

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
