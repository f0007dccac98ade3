"""Pages the window's decode steps used, as a share of the pages their
kernel visits: the summed ``pages_live`` (pages holding the active slots'
KV) over the summed ``pages_grid`` (every page of every slot) of the
``decode_tick`` spans that ended in the window.  A program whose spans
carry no such counts reads nothing."""


def read(run):
    live = grid = 0
    for _, _, a in run.window_spans("decode_tick"):
        if "pages_live" not in a or "pages_grid" not in a:
            return None
        live += a["pages_live"]
        grid += a["pages_grid"]
    return 100.0 * live / grid if grid else None
