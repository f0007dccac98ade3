"""Dense-model FLOPs of every token processed in the window (each prompt
chunk that ended in it, and each token a decode step served in it), per
second of the window, as a share of the chip's peak bf16 rate.  It counts
the dense model's work, so an SPLS step that skips work is charged the
same as a dense one."""

import flops


def read(run):
    s = run.shape
    total = sum(flops.prefill_flops(s, a["start"], a["valid"])
                for _, _, a in run.window_spans("prefill_chunk"))
    total += sum(flops.decode_flops(s, kv) for _, kv in run.decode_tokens())
    if total <= 0:
        return None
    return 100.0 * total / run.span_s / run.peak["bf16_flops_per_s"]
