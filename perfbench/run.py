"""Benchmark entry point.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the accelerator this process finds and
prints one JSON result line last on standard output (see harness.py).
"""
import time

PROCESS_T0 = time.perf_counter()

import sys  # noqa: E402

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], process_t0=PROCESS_T0))
