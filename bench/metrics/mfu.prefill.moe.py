"""The chip share's model FLOPs of every token processed in the window
(each prompt chunk that ended in it, and each token a decode step served
in it), per second of the window, as a share of the chip's peak bf16
rate.  A token counts its attention, the router over every expert, and
the pairs it is expected to route to the experts held here
(``flops_moe``); the LM head counts once a chunk and once a decoded
token."""

import flops_moe


def read(run):
    s = flops_moe.MoeShape.from_conf(run.cell.conf)
    total = sum(flops_moe.prefill_flops(s, a["start"], a["valid"])
                for _, _, a in run.window_spans("prefill_chunk"))
    total += sum(flops_moe.decode_flops(s, kv)
                 for _, kv in run.decode_tokens())
    if total <= 0:
        return None
    return 100.0 * total / run.span_s / run.peak["bf16_flops_per_s"]
