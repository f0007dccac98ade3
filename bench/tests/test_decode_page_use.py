"""The ``decode_page_use`` reader on a hand-made run record."""

import numpy as np
import pytest

import flops
import harness

SHAPE = flops.Shape(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4,
                    d_ff=16, vocab=32)


def make_run(spans):
    return harness.Run(cell=None, seed=0, shape=SHAPE, peak={},
                       setup_s=1.0, t0=100.0, t1=110.0, recs=[],
                       ticks=[], spans=list(spans))


def tick(b, e, **args):
    return [{"ph": "B", "name": "decode_tick", "ts": b, "tid": 0,
             "args": dict(n_active=1, **args)},
            {"ph": "E", "name": "decode_tick", "ts": e, "tid": 0}]


def test_live_over_visited_pages_in_the_window():
    spans = (tick(99.0, 99.5, pages_live=50, pages_grid=100)   # before
             + tick(100.5, 101.0, pages_live=10, pages_grid=200)
             + tick(101.5, 102.0, pages_live=30, pages_grid=200))
    read = harness.reader("decode_page_use")
    assert read(make_run(spans)) == pytest.approx(100.0 * 40 / 400)


def test_reads_nothing_without_the_counts():
    read = harness.reader("decode_page_use")
    # the spans of a program that does not count pages
    assert read(make_run(tick(100.5, 101.0))) is None
    assert read(make_run([])) is None
    assert np.isfinite(read(make_run(
        tick(100.5, 101.0, pages_live=0, pages_grid=8))))
