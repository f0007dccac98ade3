"""Tokens served in the window, first tokens included, per second."""


def read(run):
    n = sum(1 for r in run.recs for t in r.times if run.in_window(t))
    return n / run.span_s
