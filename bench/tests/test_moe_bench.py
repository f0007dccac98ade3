"""``flops_moe`` against hand counts on a toy MoE shape, and the three
readers of the MoE cell on hand-made run records: each reads its number,
and nothing where its spans, counters or trace are absent."""

import json
import os
import types

import numpy as np
import pytest

import flops_moe
import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# 2 layers, d 8, 2 heads of 4 (MHA), experts of width 16, vocab 32; the
# router spans 8 experts, 2 per token, experts 0 and 1 held here
CONF = {"model": {"num_hidden_layers": 2, "hidden_size": 8,
                  "num_attention_heads": 2, "num_key_value_heads": 2,
                  "head_dim": 4, "intermediate_size": 16, "vocab_size": 32},
        "deployment": {"router_experts": 8, "experts_per_token": 2,
                       "held_experts": [0, 1]}}
S = flops_moe.MoeShape.from_conf(CONF)
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


def test_expert_counts():
    assert flops_moe.pair_flops(S) == 2 * 3 * 8 * 16 == 768
    assert flops_moe.expert_bytes(S) == 3 * 8 * 16 * 2 == 768
    # 10 pairs over 3 touched experts: 3 experts' weights, 10 rows in
    # (bf16) and out (f32)
    assert flops_moe.gmm_cost(S, 10, 3) == (7680, 3 * 768 + 10 * 8 * 6)


def test_token_and_step_flops():
    # per layer: q, k, v, o 2*(64+128+64) = 512; router 2*8*8 = 128;
    # expected held pairs 2 * 2/8 = 0.5 of 768 = 384
    assert flops_moe.token_flops(S) == 2 * (512 + 128 + 384) == 2048
    assert flops_moe.attention_flops(S, 1) == 4 * 2 * 4 * 2 == 64
    assert flops_moe.head_flops(S) == 2 * 8 * 32 == 512
    # 4 rows from 0 attend 1+2+3+4 keys; the head once
    assert flops_moe.prefill_flops(S, 0, 4) == 4 * 2048 + 64 * 10 + 512
    assert flops_moe.decode_flops(S, 10) == 2048 + 640 + 512


def test_the_cell_config_reads():
    with open(os.path.join(BENCH, "configs", "olmoe-1b-7b-ep8.json")) as f:
        s = flops_moe.MoeShape.from_conf(json.load(f))
    assert (s.layers, s.d_model, s.router_experts, s.topk, s.held) == (
        16, 2048, 64, 8, 8)
    # one held expert's weights in bf16: 12.6 MB
    assert flops_moe.expert_bytes(s) == 12_582_912


def rec(times, lp=10):
    r = harness.Rec(rid=0, prompt=np.zeros((lp,), np.int32), max_new=4,
                    phase="window", due=99.0, submit=99.0)
    r.times = list(times)
    return r


def span(name, b, e, tid=1, **args):
    return [{"ph": "B", "name": name, "ts": b, "tid": tid, "args": args},
            {"ph": "E", "name": name, "ts": e, "tid": tid}]


def make_run(spans=(), recs=(), trace=None):
    return harness.Run(cell=types.SimpleNamespace(conf=CONF), seed=0,
                       shape=None, peak=PEAK, setup_s=1.0, t0=100.0,
                       t1=110.0, recs=list(recs), ticks=[], spans=list(spans),
                       trace=trace)


COUNTED = (span("prefill_chunk", 100.5, 101.0, start=0, valid=4,
                moe_pairs=16, moe_touched=3, moe_peak=8)
           + span("prefill_chunk", 101.0, 101.5, start=4, valid=2,
                  moe_pairs=8, moe_touched=2, moe_peak=2)
           + span("decode_tick", 101.6, 102.0, tid=0, n_active=1,
                  moe_pairs=2, moe_touched=2, moe_peak=1)
           # ends before the window opens: not counted
           + span("prefill_chunk", 99.0, 99.5, start=0, valid=4,
                  moe_pairs=99, moe_touched=4, moe_peak=99))
BARE = (span("prefill_chunk", 100.5, 101.0, start=0, valid=4)
        + span("decode_tick", 101.6, 102.0, tid=0, n_active=1))


def trace(ns):
    return types.SimpleNamespace(op_ns={"moe_gmm": ns, "fusion": 5e6})


def test_moe_gmm_roofline():
    # every step bound by bytes: 3*768+16*48, 2*768+8*48, 2*768+2*48
    least = ((3 * 768 + 16 * 48) + (2 * 768 + 8 * 48)
             + (2 * 768 + 2 * 48)) / 1e9
    read = harness.reader("moe_gmm_roofline")
    assert read(make_run(COUNTED, trace=trace(2 * least * 1e9))) \
        == pytest.approx(50.0)
    assert read(make_run(COUNTED)) is None               # no trace
    assert read(make_run(COUNTED, trace=types.SimpleNamespace(
        op_ns={"fusion": 1.0}))) is None                 # no kernel
    assert read(make_run(BARE, trace=trace(1e6))) is None  # no counters


def test_moe_load_peak():
    # peak over the mean held expert's pairs in a layer (pairs / (2 * 2)):
    # 8 / 4 and 2 / 2
    read = harness.reader("moe_load_peak")
    assert read(make_run(COUNTED)) == pytest.approx((2.0 + 1.0) / 2)
    assert read(make_run(BARE)) is None
    assert read(make_run()) is None


def test_mfu_prefill_moe():
    # the two window chunks and the decode token served at 101.0 (its
    # query attends the 10 prompt tokens and one served)
    run = make_run(COUNTED, recs=[rec([100.8, 101.0, 111.0])])
    want = (flops_moe.prefill_flops(S, 0, 4) + flops_moe.prefill_flops(S, 4, 2)
            + flops_moe.decode_flops(S, 11)) / 10.0 / 1e12 * 100
    assert harness.reader("mfu.prefill.moe")(run) == pytest.approx(want)
    assert harness.reader("mfu.prefill.moe")(make_run()) is None
