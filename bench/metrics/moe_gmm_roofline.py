"""The ``moe_gmm`` kernel's share of its roofline: the least time the chip
needs for the held experts' work of the window's chunk and decode steps,
over the kernel's device time in the trace.  A step's least time is the
larger of its routed pairs' FLOPs at peak and, at HBM bandwidth, its
touched experts' weights read once plus each pair's input and output
rows (``flops_moe.gmm_cost`` of the ``moe_pairs`` and ``moe_touched``
its span carries).  A run without the kernel or the counters reads
nothing."""

import flops_moe

KERNEL = "moe_gmm"
SPANS = ("prefill_chunk", "decode_tick")


def read(run):
    if run.trace is None:
        return None
    dev_s = run.trace.op_ns.get(KERNEL, 0.0) / 1e9
    if dev_s <= 0:
        return None
    s = flops_moe.MoeShape.from_conf(run.cell.conf)
    least = 0.0
    for name in SPANS:
        for _, _, a in run.window_spans(name):
            if "moe_pairs" not in a:
                return None
            least += flops_moe.roofline_seconds(
                *flops_moe.gmm_cost(s, a["moe_pairs"], a["moe_touched"]),
                run.peak)
    return 100.0 * least / dev_s if least > 0 else None
