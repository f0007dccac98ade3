"""The ``paged_flash_decode`` kernel's share of its roofline: the least
time the chip needs for the decode attention of the window's decode
steps (every K and V row attended read once, bounded by HBM bandwidth or
peak FLOP/s, per call), over the kernel's device time in the trace."""

import collections

import flops

KERNEL = "paged_flash_decode"


def read(run):
    if run.trace is None:
        return None
    dev_s = run.trace.op_ns.get(KERNEL, 0.0) / 1e9
    if dev_s <= 0:
        return None
    per_tick = collections.defaultdict(list)
    for t, kv in run.decode_tokens():
        per_tick[t].append(kv)
    least = sum(flops.roofline_seconds(*flops.paged_decode_cost(run.shape,
                                                                 kvs),
                                       run.peak)
                for kvs in per_tick.values())
    return 100.0 * least / dev_s
