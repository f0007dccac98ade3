"""95th percentile of every gap between two consecutive tokens of one
request, pooled over all requests, for gaps that end in the window."""

import numpy as np


def read(run):
    gaps = [b - a for r in run.recs for a, b in zip(r.times, r.times[1:])
            if run.in_window(b)]
    return 1e3 * float(np.percentile(gaps, 95)) if gaps else None
