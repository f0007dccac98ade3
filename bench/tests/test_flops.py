"""The FLOP and byte functions against hand counts at Qwen3-0.6B shapes
(28 layers, d 1024, 16 query / 8 KV heads of 128, d_ff 3072, vocab
151936)."""

import json
import os

import flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def shape():
    with open(os.path.join(BENCH, "configs", "qwen3-0.6b-dense.json")) as f:
        return flops.Shape.from_model(json.load(f)["model"])


def test_linear_flops_per_token():
    # per layer: q 1024*2048, k+v 2*1024*1024, o 2048*1024, ffn 3*1024*3072
    # = 15,728,640 MACs; x2 FLOPs x 28 layers
    assert flops.linear_flops_per_token(shape()) == 880_803_840


def test_decode_flops():
    # linear + head 2*1024*151936 + attention 4*16*128*28 per key
    assert flops.head_flops(shape()) == 311_164_928
    assert flops.attention_flops(shape(), 1) == 229_376
    assert flops.decode_flops(shape(), 1000) == 1_421_344_768


def test_prefill_flops_first_chunk():
    # 64 rows, row t attends t+1 keys: 2080 keys in all; one head row
    assert flops.prefill_flops(shape(), 0, 64) == 57_159_712_768
    # a later chunk attends the earlier rows too
    later = flops.prefill_flops(shape(), 64, 64)
    assert later - flops.prefill_flops(shape(), 0, 64) == 229_376 * 64 * 64


def test_paged_decode_cost():
    f, b = flops.paged_decode_cost(shape(), [1000])
    assert f == 229_376_000
    # K and V: 2 * 8 * 128 * 2 bytes per key per layer; q and out rows
    assert b == 2 * 8 * 128 * 2 * 1000 * 28 + 2 * 16 * 128 * 2 * 28
    with open(os.path.join(BENCH, "peaks.json")) as fh:
        peak = json.load(fh)["TPU v5 lite"]
    # memory bound: 57.6 MB at 819 GB/s
    assert abs(flops.roofline_seconds(f, b, peak) - b / 819e9) < 1e-15
