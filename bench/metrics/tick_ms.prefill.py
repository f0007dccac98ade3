"""Mean engine tick, host clock: the window's span over the ticks that
ended in it (every tick runs at most one prefill chunk)."""


def read(run):
    n = sum(1 for t in run.ticks if run.in_window(t))
    return 1e3 * run.span_s / n if n else None
