"""Run one benchmark cell once and print its result as one JSON line.

    python3 perfbench/run.py --workload douban-r20.uniform --seed 7 \
        --seconds 30 --trace 0

The cells, their deployments and traffic mixes are named in
``BENCHMARK.json`` at the checkout root.  Without an accelerator (or with
fewer chips than the cell asks for) it exits non-zero and prints no
result.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
