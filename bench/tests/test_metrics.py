"""The metric readers on a hand-made run record."""

import numpy as np
import pytest

import flops
import harness

SHAPE = flops.Shape(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4,
                    d_ff=16, vocab=32)
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


def rec(rid, due, times, lp=10):
    r = harness.Rec(rid=rid, prompt=np.zeros((lp,), np.int32), max_new=4,
                    phase="window", due=due, submit=due)
    r.times = list(times)
    return r


def make_run(recs, spans=(), ticks=(), t0=100.0, t1=110.0):
    return harness.Run(cell=None, seed=0, shape=SHAPE, peak=PEAK,
                       setup_s=12.5, t0=t0, t1=t1, recs=list(recs),
                       ticks=list(ticks), spans=list(spans))


def span(name, b, e, tid=1, **args):
    return [{"ph": "B", "name": name, "ts": b, "tid": tid, "args": args},
            {"ph": "E", "name": name, "ts": e, "tid": tid}]


def test_rates_and_setup():
    spans = (span("prefill_chunk", 100.5, 101.0, start=0, valid=64)
             + span("prefill_chunk", 101.0, 101.5, start=64, valid=30)
             + span("prefill_chunk", 99.0, 99.5, start=0, valid=64))
    run = make_run([rec(0, 99.0, [101.5, 102.0, 111.0])], spans=spans)
    assert harness.reader("prompt_tok_s")(run) == pytest.approx(94 / 10)
    assert harness.reader("output_tok_s")(run) == pytest.approx(2 / 10)
    assert harness.reader("setup_s")(run) == 12.5


def test_itl_tail():
    recs = [rec(i, 100.0 + i, [100.0 + i + 0.1 * (i + 1), 102.0 + i])
            for i in range(5)]
    recs.append(rec(9, 109.0, []))          # no token by the close
    run = make_run(recs, ticks=[100.5, 105.0, 110.0, 111.0])
    gaps = [(102.0 + i) - (100.0 + i + 0.1 * (i + 1)) for i in range(5)]
    assert harness.reader("itl_p95_ms")(run) == pytest.approx(
        1e3 * float(np.percentile(gaps, 95)))
    assert harness.reader("tick_ms.prefill")(run) == pytest.approx(
        1e3 * 10.0 / 3)


def test_decode_flops_and_mfu():
    # token k >= 1 of a request attends to its prompt and k served tokens
    run = make_run([rec(0, 99.0, [100.5, 101.0, 102.0])],
                   spans=span("decode_tick", 100.6, 101.0, tid=0)
                   + span("decode_tick", 101.6, 102.0, tid=0))
    assert run.decode_tokens() == [(101.0, 11), (102.0, 12)]
    want = (flops.decode_flops(SHAPE, 11) + flops.decode_flops(SHAPE, 12)) \
        / 0.8 / 1e12 * 100
    assert harness.reader("mfu.decode")(run) == pytest.approx(want)


def test_trace_metrics_absent_without_trace():
    run = make_run([rec(0, 99.0, [100.5, 101.0])])
    for name in ("idle_share.prefill", "idle_share.decode",
                 "paged_flash_decode_roofline"):
        assert harness.reader(name)(run) is None


def test_judge_needs_every_number_under_its_limit():
    assert harness.judge({"max_gap": 0.1}, {"max_gap": 0.2})
    assert not harness.judge({"max_gap": 0.3}, {"max_gap": 0.2})
    assert not harness.judge({}, {"max_gap": 0.2})
    assert not harness.judge({"mean_gap": 0.0}, {"max_gap": 0.2})
