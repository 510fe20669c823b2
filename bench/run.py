"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object; the last
lines of standard error are the numbers compared for ``correct``, each with
its limit.  With no TPU, or fewer chips than the cell asks for, it prints no
result and exits with code 3.  ``BENCHMARK.json`` lists the cells.
"""

import time

T_START = time.monotonic()  # set-up is timed from here

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
