#!/usr/bin/env python3
"""The control of a cell's check, run on the chip at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 --seconds 20

For each seed, in one process: a run of the cell (the benchmark's own
path and traffic, a window of ``--seconds``), then on the same sampled
requests both the program's numbers and the control's -- the reference
at fp8 in the program's place, reading at each position the gap of the
token it ranks first.  Both are judged against the configuration's limits
by the one verdict that decides ``correct`` (``harness.judge``).  One JSON
line per seed on standard output: ``correct`` for the program,
``control_correct`` for the control, which has to read false.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    import harness

    cell = harness.Cell.find(args.workload)
    for seed in args.seeds:
        out = harness.run_cell(cell, seed, args.seconds, False,
                               time.monotonic(), control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "control_correct":
                              out["checks"]["control"]["correct"],
                          "checks": out["checks"],
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
