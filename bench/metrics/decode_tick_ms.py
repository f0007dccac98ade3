"""Mean duration of the program's ``decode_tick`` spans that ended in the
window (each ends after the tokens are read back)."""

import numpy as np


def read(run):
    d = [e - b for b, e, _ in run.window_spans("decode_tick")]
    return 1e3 * float(np.mean(d)) if d else None
