"""Read the numbers that decide ``correct`` on several seeds in one process:
the program's own runs and the control's.

    python3 bench/control.py --workload scan-count --seeds 1,2,3 \\
        --seconds 30 [--program]

The control is the cell's window run through the program's k = 1 path
(``control=True`` in the traffic kind), which breaks the configuration's
k = 0 guarantee; it has to come out not correct on every seed.  With
``--program`` each seed's sound run comes first.  Prints one JSON line per
run with its checks.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args()
    cell = harness.find_cell(args.workload)
    try:
        devices = harness.require_chips(int(cell["chips"]))
    except harness.NoChip as e:
        harness.log(str(e))
        return harness.NO_CHIP_EXIT
    harness.configure_cache()
    kind = harness.traffic_kind(cell["traffic"]["kind"])
    sides = ([False] if args.program else []) + [True]
    for seed in (int(s) for s in args.seeds.split(",")):
        for control in sides:
            run = harness.Run(cell, seed, args.seconds, False,
                              time.monotonic(), devices)
            out = kind.run(run, control=control)
            print(json.dumps({
                "seed": seed, "control": control, "correct": out.correct,
                "attempted": out.attempted, "failed": out.failed,
                "checks": {k: v for k, (v, _lim) in out.checks.items()},
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
