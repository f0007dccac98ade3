"""The trace reduction on a small recorded trace (``data/tiny.xplane.pb``,
made by ``record_trace.py`` on a TPU v5 lite): three steps of a 2048^3
bf16 matmul, each followed by a 20 ms host wait in a ``bench/host_wait``
annotation."""

import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny.xplane.pb")


@pytest.fixture(scope="module")
def pd():
    return tr.load(DATA)


def test_host_spans(pd):
    names = [n for n, _, _ in tr.host_spans(pd)]
    assert names == ["bench/tick", "bench/host_wait"] * 3


def test_busy_ops_and_gaps(pd):
    spans = tr.host_spans(pd)
    # the whole trace: from before the first op to the last wait's end
    ops = tr.device_ops(pd)["/device:TPU:0"]
    lo = min(o.start for o in ops) - 1
    r = tr.reduce(pd, window=(lo, spans[-1][2]))
    assert r.devices == 1
    # three matmul fusions of about 91.6 us each
    assert r.op_ns["fusion"] == pytest.approx(3 * 91_630, rel=0.01)
    assert r.busy_ns == pytest.approx(sum(
        e - s for s, e in tr._merge([(o.start, o.start + o.dur)
                                     for o in ops])))
    assert r.busy_ns < r.window[1] - r.window[0]
    # the three longest idle gaps are the host waits, about 20 ms each
    assert [n for n, _ in r.gaps[:3]] == ["bench/host_wait"] * 3
    assert all(19e6 < ns < 25e6 for _, ns in r.gaps[:3])
    b = tr.breakdown(r)
    assert b["device_ops"][0][0] == "fusion"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_window_clips(pd):
    ops = tr.device_ops(pd)["/device:TPU:0"]
    first = min(ops, key=lambda o: o.start)
    r = tr.reduce(pd, window=(first.start, first.start + first.dur / 2))
    assert r.busy_ns == pytest.approx(first.dur / 2, abs=1)


def test_op_label():
    assert tr.op_label("%fusion.12 = bf16[2] fusion(x)") == "fusion"
    assert tr.op_label("%paged_flash_decode.4 = bf16[32,2,128] "
                       "custom-call(a), custom_call_target=\"tpu_custom_"
                       "call\"") == "paged_flash_decode"
