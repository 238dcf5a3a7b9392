#!/usr/bin/env python3
"""Benchmark entry: run one cell of BENCHMARK.json on the chips it asks for.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The last line of standard output is the
result, one JSON object; the numbers compared with the reference are
also the last lines of standard error. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""

import time

STARTED = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
for path in (HERE, HERE.parent / "src", HERE.parent):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

from harness.main import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(started=STARTED))
