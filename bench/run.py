"""Run one benchmark cell once on the machine it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in BENCHMARK.json. The run needs as many
TPU chips as the cell asks for; without them it exits 1 and prints no
result. Its last line on standard output is the result as one JSON
object; the compared numbers and their limits are the last lines on
standard error.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
