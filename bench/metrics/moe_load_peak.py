"""How unevenly the window's chunk steps loaded the held experts: for each
``prefill_chunk`` span that ended in the window, the most pairs any held
expert got in any layer (``moe_peak``) over the mean a held expert got in
a layer (``moe_pairs`` over held experts times layers); the mean of that
over the steps.  1 is perfectly even.  A run whose spans carry no such
counters reads nothing."""

import flops_moe


def read(run):
    s = flops_moe.MoeShape.from_conf(run.cell.conf)
    ratios = []
    for _, _, a in run.window_spans("prefill_chunk"):
        if "moe_pairs" not in a:
            return None
        if a["moe_pairs"] > 0:
            ratios.append(a["moe_peak"] * s.held * s.layers / a["moe_pairs"])
    return sum(ratios) / len(ratios) if ratios else None
