"""Benchmark entry point.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the accelerator this process finds
and prints one JSON result line last on standard output.  See
``bench/harness.py``.
"""
import time

PROCESS_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import main  # noqa: E402

if __name__ == "__main__":
    main(sys.argv[1:], PROCESS_START)
