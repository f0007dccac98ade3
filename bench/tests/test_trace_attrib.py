"""The trace attribution on the small recorded trace (``data/tiny.xplane.pb``,
see ``test_trace_reduce.py``): three calls of the program ``jit_step``, a
2048^3 bf16 matmul under the named scope ``bench_matmul``, each followed
by a 20 ms host wait in a ``bench/host_wait`` annotation."""

import os

import pytest

import trace_attrib as ta
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny.xplane.pb")
VOCAB = ("bench_matmul", "bench_scale")


@pytest.fixture(scope="module")
def pd():
    return tr.load(DATA)


@pytest.fixture(scope="module")
def meta():
    return ta.event_metadata(DATA)


@pytest.fixture(scope="module")
def whole(pd):
    """The window ``test_trace_reduce`` reads: from before the first op to
    the last wait's end."""
    ops = tr.device_ops(pd)["/device:TPU:0"]
    return (min(o.start for o in ops) - 1, tr.host_spans(pd)[-1][2])


@pytest.fixture(scope="module")
def attrib(pd, meta, whole):
    return ta.attribute(pd, meta, window=whole, stages=VOCAB)


def _module_events(pd):
    for plane in pd.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Modules":
                    return list(line.events)
    raise AssertionError("no XLA Modules line")


def test_program_calls_are_the_modules_line(pd, attrib, whole):
    mods = _module_events(pd)
    assert {ta.program_name(ev.name)[0] for ev in mods} == {"jit_step"}
    # each call's device time, clipped to the window
    lo, hi = whole
    ns = sum(min(ev.start_ns + ev.duration_ns, hi) - max(ev.start_ns, lo)
             for ev in mods)
    assert ns == pytest.approx(sum(ev.duration_ns for ev in mods), abs=5)
    assert attrib.programs == {"jit_step": {"calls": 3, "ns": ns}}
    assert attrib.mean_call_ms("jit_step") == pytest.approx(ns / 3 / 1e6)
    assert attrib.mean_call_ms("jit_nothing") is None
    # every busy nanosecond lies inside a call
    assert attrib.coverage == pytest.approx(1.0)


def test_fusion_maps_to_its_named_scope(meta):
    plane = meta["/device:TPU:0"]
    (text,) = [t for t in plane if t.startswith("%fusion")]
    pid = ta.program_name(_module_events(tr.load(DATA))[0].name)[1]
    op = ta.tf_op_of(plane[text], pid)
    assert op == "jit(step)/bench_matmul/dot_general"
    assert ta.stage_of(op, VOCAB) == "bench_matmul"
    # the program's own vocabulary has no such stage
    assert ta.stage_of(op) == ta.OTHER


def test_copy_without_tf_op_is_unscoped(meta, attrib):
    plane = meta["/device:TPU:0"]
    (text,) = {t for t in plane if t.startswith("%copy-start")}
    # two metadata entries (the op and its async copy), neither scoped
    assert len(plane[text]) == 2
    assert ta.stage_of(ta.tf_op_of(plane[text])) == ta.UNSCOPED
    assert set(attrib.program_stage_ns) == {("jit_step", "bench_matmul"),
                                            ("jit_step", ta.UNSCOPED)}
    assert attrib.program_stage_ns[("jit_step", "bench_matmul")] == \
        pytest.approx(3 * 91_630, rel=0.01)
    assert set(attrib.op_stage_ns) == {("fusion", "bench_matmul"),
                                       ("copy-start", ta.UNSCOPED),
                                       ("copy-done", ta.UNSCOPED)}
    shares = attrib.stage_share("bench_matmul") + \
        attrib.stage_share(ta.UNSCOPED)
    assert shares == pytest.approx(100.0)


def test_disagreeing_entries_are_ambiguous():
    entries = [("1", "jit(a)/qkv/dot"), ("2", "jit(b)/ffn/dot")]
    assert ta.tf_op_of(entries) == ta.AMBIGUOUS
    assert ta.stage_of(ta.tf_op_of(entries)) == ta.AMBIGUOUS
    # narrowed to its program, the op is known
    assert ta.stage_of(ta.tf_op_of(entries, "2")) == "ffn"
    assert ta.stage_of(
        "jit(s)/kv_pool/while/body/closed_call/qkv/dot_general") == "qkv"
    assert ta.stage_of(
        "jit(s)/ffn/gathered_matmul/buffered/pallas_call") == "ffn"


def test_idle_goes_to_the_host_waits(attrib):
    idle = attrib.idle_ns
    total = sum(idle.values())
    assert total == pytest.approx(attrib.window_ns - attrib.busy_ns)
    assert max(idle, key=idle.get) == "bench/host_wait"
    assert 3 * 19e6 < idle["bench/host_wait"] < 3 * 25e6
    assert attrib.ticks == 0 and attrib.engine_idle_ms_per_tick() is None
    assert attrib.named_idle_share() == pytest.approx(
        1 - idle[ta.NO_SPAN] / total)


def test_trace_reduce_reads_as_before(pd, whole, attrib):
    """The reduction the ledger reads is untouched by the attribution."""
    r = tr.reduce(pd, window=whole)
    assert r.window == (45445778.0, 111009683.0)
    assert r.busy_ns == 297915.0 == attrib.busy_ns
    assert r.op_ns == {"copy-start": 41.0, "copy-done": 23002.0,
                       "fusion": 274872.0}
    assert [n for n, _ in r.gaps[:3]] == ["bench/host_wait"] * 3
    assert [ns for _, ns in r.gaps[:3]] == [22512579.0, 21578412.0,
                                            21174992.0]


def test_cli_prints_the_tables(capsys):
    assert ta.main([DATA]) == 0
    text = capsys.readouterr().out
    assert "program x stage" in text and "jit_step" in text
    assert "ops by device time" in text and "copy-start" in text
    assert "device idle by host phase" in text and "bench/host_wait" in text


def test_phase_segments_prefer_the_innermost_engine_span():
    spans = [("bench/tick", 0, 100), ("engine/tick", 10, 90),
             ("engine/decode_readback", 40, 60), ("bench/submit", 95, 98)]
    seg = ta._phase_segments(spans, 0, 120)
    assert seg == [(0, 10, "bench/tick"), (10, 40, "engine/tick"),
                   (40, 60, "engine/decode_readback"),
                   (60, 90, "engine/tick"), (90, 95, "bench/tick"),
                   (95, 98, "bench/submit"), (98, 100, "bench/tick"),
                   (100, 120, ta.NO_SPAN)]
    idle = ta._idle_by_phase([(5, 15), (45, 50), (99, 110)], seg)
    assert idle == {"bench/tick": 6, "engine/tick": 5,
                    "engine/decode_readback": 5, ta.NO_SPAN: 10}


def test_wire_reader_decodes_varints_and_lengths():
    # field 1 varint 300; field 2 string "ab"; field 3 fixed64; field 4
    # fixed32
    buf = bytes([0x08, 0xAC, 0x02, 0x12, 0x02, 0x61, 0x62,
                 0x19] + [0] * 8 + [0x25, 1, 0, 0, 0])
    got = list(ta._fields(memoryview(buf)))
    assert got[0] == (1, 0, 300)
    assert got[1][:2] == (2, 2) and ta._text(buf, got[1][2]) == "ab"
    assert got[2] == (3, 1, bytes(8)) and got[3] == (4, 5, bytes([1, 0, 0,
                                                                  0]))
    with pytest.raises(ValueError):
        list(ta._fields(memoryview(bytes([0x0B]))))
