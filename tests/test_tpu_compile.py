"""Every Pallas kernel the TPU ``auto`` backends can select, compiled for
a described (not attached) TPU v5e chip at published widths.

Interpret mode accepts block shapes and DMA slices the TPU compiler
refuses, so each main-path kernel is compiled here with
``interpret=False`` and checked to lower to a Mosaic ``tpu_custom_call``:

* Qwen3-0.6B serving widths -- 16 query / 8 KV heads of dim 128, d_model
  1024, d_ff 3072, page size 16, prefill chunk 64;
* OLMoE-1B-7B's chip share (olmoe-1b-7b-ep8) -- 16 query and 16 KV heads
  of dim 128 (G = 1), 8 held experts of width 1024 at d_model 2048, 8
  slots of 4096 tokens, prefill chunk 1024;
* bert-base-esact's non-causal attention (12 heads of dim 64, L = 512).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, so every test worker must
collect the same tests and only the worker running this file loads it.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.gathered_matmul import gather_rows_kernel, gathered_matmul
from repro.kernels.moe_gmm import moe_gmm, tile_rows
from repro.kernels.paged_decode import paged_flash_decode

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
# Qwen3-0.6B (configs/qwen3_0_6b.py) at the serving engine's page/chunk
H, KV, DH, D, FF = 16, 8, 128, 1024, 3072
PAGE, CHUNK, SLOTS, MAX_LEN = 16, 64, 4, 1024
N_PAGES = SLOTS * MAX_LEN // PAGE + 1
# olmoe-1b-7b-ep8 (configs/olmoe_1b_7b_ep8.py) at its benchmark cell
OL_KV, OL_D, OL_F, OL_E, OL_EH, OL_K = 16, 2048, 1024, 64, 8, 8
OL_SLOTS, OL_MAX, OL_CHUNK = 8, 4096, 1024
OL_PAGES = OL_SLOTS * OL_MAX // PAGE + 1
# bert-base-esact (configs/bert_base_esact.py) at the paper's L = 512
BERT_H, BERT_DH, BERT_L = 12, 64, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the compiler's own logs would otherwise land in the temp directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's compiles cannot be read back from the persistent
    # cache without the chip, so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _flash(causal):
    return lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                           interpret=False)


def _flash_spls(causal):
    return lambda q, k, v, qp, keep: flash_attention(
        q, k, v, causal=causal, q_pos=qp, kv_keep=keep, interpret=False)


def _moe_gmm_case(tokens):
    bm = tile_rows(tokens, OL_K, OL_E)
    nt = -(-tokens * OL_K // bm) + OL_EH
    fn = lambda x, wg, wu, wd, tg, n: moe_gmm(x, wg, wu, wd, tg, n, bm=bm,
                                              interpret=False)
    return fn, [((nt * bm, OL_D), BF16), ((OL_EH, OL_D, OL_F), BF16),
                ((OL_EH, OL_D, OL_F), BF16), ((OL_EH, OL_F, OL_D), BF16),
                ((nt,), I32), ((1,), I32)]


# name -> (fn, [(shape, dtype), ...])
CASES = {
    "flash_qwen_causal": (_flash(True), [
        ((1, H, MAX_LEN, DH), BF16), ((1, KV, MAX_LEN, DH), BF16),
        ((1, KV, MAX_LEN, DH), BF16)]),
    "flash_qwen_spls": (_flash_spls(True), [
        ((1, H, MAX_LEN // 2, DH), BF16), ((1, KV, MAX_LEN, DH), BF16),
        ((1, KV, MAX_LEN, DH), BF16), ((1, H, MAX_LEN // 2), I32),
        ((1, H, MAX_LEN), jnp.bool_)]),
    "flash_bert_noncausal": (_flash(False), [
        ((1, BERT_H, BERT_L, BERT_DH), BF16)] * 3),
    "flash_bert_noncausal_spls": (_flash_spls(False), [
        ((1, BERT_H, BERT_L // 2, BERT_DH), BF16),
        ((1, BERT_H, BERT_L, BERT_DH), BF16),
        ((1, BERT_H, BERT_L, BERT_DH), BF16), ((1, BERT_H, BERT_L // 2), I32),
        ((1, BERT_H, BERT_L), jnp.bool_)]),
    "paged_flash_decode": (
        lambda q, kp, vp, pp, tb, kl, pos: paged_flash_decode(
            q, kp, vp, pp, tb, kl, pos, interpret=False), [
            ((SLOTS, KV, H // KV, DH), BF16),
            ((KV, N_PAGES, PAGE, DH), BF16), ((KV, N_PAGES, PAGE, DH), BF16),
            ((N_PAGES, PAGE), I32), ((SLOTS, MAX_LEN // PAGE), I32),
            ((SLOTS,), I32), ((SLOTS,), I32)]),
    # the benchmark's engine: 20 slots of 128 pages; and a window, whose
    # ids ride in gathered through the tables, at 100 pages a sequence
    "paged_flash_decode_20x128": (
        lambda q, kp, vp, pp, tb, kl, pos: paged_flash_decode(
            q, kp, vp, pp, tb, kl, pos, interpret=False), [
            ((20, KV, H // KV, DH), BF16),
            ((KV, 20 * 128 + 1, PAGE, DH), BF16),
            ((KV, 20 * 128 + 1, PAGE, DH), BF16),
            ((20 * 128 + 1, PAGE), I32), ((20, 128), I32),
            ((20,), I32), ((20,), I32)]),
    "paged_flash_decode_window": (
        lambda q, kp, vp, pp, tb, kl, pos: paged_flash_decode(
            q, kp, vp, pp, tb, kl, pos, window=512, interpret=False), [
            ((SLOTS, KV, H // KV, DH), BF16),
            ((KV, N_PAGES, PAGE, DH), BF16), ((KV, N_PAGES, PAGE, DH), BF16),
            ((N_PAGES, PAGE), I32), ((SLOTS, 100), I32),
            ((SLOTS,), I32), ((SLOTS,), I32)]),
    "flash_decode": (
        lambda q, k, v, pos: flash_decode(q, k, v, pos, interpret=False), [
            ((SLOTS, KV, H // KV, DH), BF16), ((SLOTS, KV, MAX_LEN, DH), BF16),
            ((SLOTS, KV, MAX_LEN, DH), BF16), ((SLOTS,), I32)]),
    # packed Q projection of a chunk: (CHUNK, D) @ (D, H*DH) on 48 rows
    "gathered_matmul_q": (
        lambda x, w, p: gathered_matmul(x, w, p, interpret=False), [
            ((CHUNK, D), BF16), ((D, H * DH), BF16), ((48,), I32)]),
    # packed FFN up-projection with the fused leader scatter
    "gathered_matmul_ffn_scatter": (
        lambda x, w, p, s: gathered_matmul(x, w, p, src_slot=s,
                                           interpret=False), [
            ((CHUNK, D), BF16), ((D, FF), BF16), ((48,), I32),
            ((CHUNK,), I32)]),
    # MHA: 16 KV heads, one query head each, 8 slots of 256 pages
    "paged_flash_decode_olmoe": (
        lambda q, kp, vp, pp, tb, kl, pos: paged_flash_decode(
            q, kp, vp, pp, tb, kl, pos, interpret=False), [
            ((OL_SLOTS, OL_KV, 1, DH), BF16),
            ((OL_KV, OL_PAGES, PAGE, DH), BF16),
            ((OL_KV, OL_PAGES, PAGE, DH), BF16),
            ((OL_PAGES, PAGE), I32), ((OL_SLOTS, OL_MAX // PAGE), I32),
            ((OL_SLOTS,), I32), ((OL_SLOTS,), I32)]),
    # the serving steps' read: layer l of the stacked (L, KV, N, ps, Dh)
    # pool, l traced -- qwen's 28 layers at 20 slots, olmoe's 16 at 8
    "paged_flash_decode_stacked": (
        lambda q, kp, vp, pp, tb, kl, pos, l: paged_flash_decode(
            q, kp, vp, pp, tb, kl, pos, layer=l, interpret=False), [
            ((20, KV, H // KV, DH), BF16),
            ((28, KV, 20 * 128 + 1, PAGE, DH), BF16),
            ((28, KV, 20 * 128 + 1, PAGE, DH), BF16),
            ((20 * 128 + 1, PAGE), I32), ((20, 128), I32),
            ((20,), I32), ((20,), I32), ((), I32)]),
    "paged_flash_decode_stacked_olmoe": (
        lambda q, kp, vp, pp, tb, kl, pos, l: paged_flash_decode(
            q, kp, vp, pp, tb, kl, pos, layer=l, interpret=False), [
            ((OL_SLOTS, OL_KV, 1, DH), BF16),
            ((16, OL_KV, OL_PAGES, PAGE, DH), BF16),
            ((16, OL_KV, OL_PAGES, PAGE, DH), BF16),
            ((OL_PAGES, PAGE), I32), ((OL_SLOTS, OL_MAX // PAGE), I32),
            ((OL_SLOTS,), I32), ((OL_SLOTS,), I32), ((), I32)]),
    # the held experts' grouped FFN of a 1024-token chunk and of a decode
    # step over 8 slots, the grid sized for every pair held
    "moe_gmm_chunk": _moe_gmm_case(OL_CHUNK),
    "moe_gmm_decode": _moe_gmm_case(OL_SLOTS),
    "gather_rows_kernel": (
        lambda src, idx: gather_rows_kernel(src, idx, interpret=False), [
            ((48, D), BF16), ((CHUNK,), I32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
