"""Dense-model FLOPs of the tokens decode steps served in the window,
over the summed duration of the window's ``decode_tick`` spans, as a
share of the chip's peak bf16 rate."""

import flops


def read(run):
    busy = sum(e - b for b, e, _ in run.window_spans("decode_tick"))
    total = sum(flops.decode_flops(run.shape, kv)
                for _, kv in run.decode_tokens())
    if busy <= 0 or total <= 0:
        return None
    return 100.0 * total / busy / run.peak["bf16_flops_per_s"]
