"""The test-only entry of the rehearsal: harness.main on whatever jax
finds (the CPU), over a root the test built. The command, benchmark/run.py,
has no such switch: it refuses anything but a TPU.

    python benchmark/tests/rehearse.py <root> <data_root> --workload ...
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[3:], T_START, root=sys.argv[1],
                          data_root=sys.argv[2], require_tpu=False))
