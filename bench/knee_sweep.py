#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest Poisson rate the
system serves without a growing backlog.

    python3 bench/knee_sweep.py --workload qwen3-0.6b-dense.chat \
        --rates 1 1.5 2 2.5 3 --seconds 20

Each rate runs the cell's own path and mix with only ``rate_per_s``
changed, in one process, without the reference check.  Per rate it
prints one JSON line: the rate offered and completed, the tokens served
per second, TTFT percentiles of the requests due in the first and second
half of the window, and the requests still waiting for a first token at
the close.  A backlog grows where the second half's TTFT keeps rising
above the first half's and requests are left waiting.  The cell's rate
is then set, once, at about 0.8 of the knee.
"""

import argparse
import copy
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def summary(run, rate):
    due = run.due_in_window()
    mid = run.t0 + run.span_s / 2

    def ttft(rs):
        w = [(r.times[0] if r.times else run.t1) - r.due for r in rs]
        return (float(np.percentile(w, 50)), float(np.percentile(w, 95))) \
            if w else (None, None)

    done = [r for r in due if r.done_t is not None and r.done_t <= run.t1]
    toks = sum(1 for r in run.recs for t in r.times if run.in_window(t))
    return {"rate_offered": rate, "due": len(due),
            "completed_per_s": len(done) / run.span_s,
            "output_tok_s": toks / run.span_s,
            "ttft_first_half_p50_p95": ttft([r for r in due if r.due < mid]),
            "ttft_second_half_p50_p95": ttft([r for r in due
                                              if r.due >= mid]),
            "waiting_at_close": sum(1 for r in due if not r.times)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import harness

    base = harness.Cell.find(args.workload)
    if base.mix["arrival"]["kind"] != "poisson":
        ap.error("the knee sweep is for open-loop (poisson) mixes")
    for rate in args.rates:
        cell = copy.deepcopy(base)
        cell.mix["arrival"]["rate_per_s"] = rate
        keep = {}
        harness.run_cell(cell, args.seed, args.seconds, False,
                         time.monotonic(), check_outputs=False, keep=keep)
        print(json.dumps(summary(keep["run"], rate)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
