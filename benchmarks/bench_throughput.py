"""Fig. 20 + Table IV: cycle-model throughput decomposition and the
attention-level energy-efficiency comparison vs SpAtten / Sanger -- plus a
*measured* serving comparison: tokens/sec and pages-in-use for the
block-pool paged engine vs paged+SPLS page pruning on the BERT-Base
(smoke-scale) config.  The derived ``req_per_mb`` column is the acceptance
metric: concurrent requests per MB of KV pool actually needed (paged+SPLS
> paged)."""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.perfmodel import (attention_level_comparison, energy_efficiency,
                             speedup_breakdown)

# paper-measured SPLS sparsity (Fig. 15 averages)
PAPER_REDUCTIONS = {"qkv": 0.6566, "attention": 0.9465, "ffn": 0.5033}

# measured serving workload (CPU smoke scale)
_N_REQ, _SLOTS, _PROMPT, _MAX_NEW, _PS = 8, 4, 48, 8, 8


def _bert_serving_cfg(spls: bool):
    from repro.configs.bert_base_esact import CONFIG
    from repro.core.spls import SPLSConfig

    cfg = dataclasses.replace(CONFIG.smoke(), remat=False, causal=True)
    spls_cfg = SPLSConfig(enabled=spls, k_ratio=0.12, s_threshold=0.6,
                          f_threshold=2, window=4, causal=True)
    return dataclasses.replace(cfg, spls=spls_cfg)


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _measure_engine(mode: str, telemetry: bool = True):
    """mode: paged | paged_spls | paged_chunked |
    paged_spls_chunked.  The ``*_chunked`` variants prefill long prompts
    in 16-token chunks (interleaved with decode); ``paged_spls_chunked``
    is the progressive-SPLS serving path -- the plan streams per chunk and
    kept KV columns compact at end of prefill.  Returns ``(us, derived,
    engine, outputs)``; ``telemetry=False`` measures the no-op-sink
    engine for the overhead row."""
    from repro.models import init_params
    from repro.serving import PagedServingEngine, Request, ServeConfig

    chunked = mode.endswith("_chunked")
    spls = mode.startswith("paged_spls")
    cfg = _bert_serving_cfg(spls)
    params = init_params(cfg, jax.random.PRNGKey(0))
    max_len = _PROMPT + _MAX_NEW + _PS
    scfg = ServeConfig(n_slots=_SLOTS, max_len=max_len, page_size=_PS,
                       attn_backend="xla_paged_decode",
                       prefill_chunk=16 if chunked else 64,
                       spls_page_prune=spls, spls_prune_vote=1.0,
                       telemetry=telemetry)
    eng = PagedServingEngine(cfg, params, scfg)
    reqs = []
    for i in range(_N_REQ):
        prompt = jax.random.randint(jax.random.PRNGKey(200 + i), (_PROMPT,),
                                    0, cfg.vocab_size)
        r = Request(rid=i, prompt=prompt, max_new_tokens=_MAX_NEW)
        reqs.append(r)
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run_until_drained(max_ticks=2000)
    dt = time.perf_counter() - t0
    tokens = sum(len(r.output) for r in reqs)
    assert all(r.done for r in reqs)

    # the SPLS predictor cache is page-parallel pool memory: charge it
    # (int8 codes + per-token scale since the planner unification --
    # _tree_bytes naturally reports the reduced footprint)
    pool_bytes = _tree_bytes(eng.cache)
    if eng.pred_cache is not None:
        pool_bytes += _tree_bytes(eng.pred_cache)
    page_bytes = pool_bytes / eng.pool.n_pages
    kv_bytes = int(eng.stats["peak_pages"] * page_bytes)
    saved = eng.stats["flops_saved_pct"]
    out = {"tok_s": round(tokens / dt, 1),
           "kv_mb": round(kv_bytes / 1e6, 4),
           "concurrent": _SLOTS,
           "req_per_mb": round(_SLOTS / (kv_bytes / 1e6), 2),
           # lifetime prefill-compute savings (scheduler accounting);
           # dense compute executes everything, so these stay 0.0 until a
           # packed compute backend is active
           "flops_saved_qkv_pct": round(saved["qkv"], 1),
           "flops_saved_attn_pct": round(saved["attn"], 1),
           "flops_saved_ffn_pct": round(saved["ffn"], 1),
           "flops_saved_kv_pct": round(saved.get("kv", 0.0), 1),
           "pages_in_use_peak": eng.stats["peak_pages"]}
    return dt * 1e6, out, eng, [list(r.output) for r in reqs]


# end-to-end sparse prefill comparison (serving width): bert-smoke
# architecture widened to a serving-shaped d_model/d_ff so the packed
# matmul savings are measurable above CPU dispatch noise
_PK_PROMPT, _PK_CHUNK, _PK_REQS, _PK_NEW = 128, 32, 6, 2


def _measure_packed_prefill(compute_backend: str,
                            vote_horizon=None):
    """Prefill-heavy chunked+SPLS serving run; compute_backend "dense" is
    the baseline, "packed_xla" the end-to-end sparse path (same engine,
    same plan, only the compute execution differs).  ``vote_horizon=1``
    adds the horizon-finalized prune vote: a chunk's own columns that
    miss the cross-head bar on their own plan block skip the K/V
    projection entirely (core.planner; bounded divergence from the
    end-of-prefill vote, measured here as flops_saved_kv_pct > 0)."""
    from repro.models import init_params
    from repro.serving import PagedServingEngine, Request, ServeConfig

    cfg = _bert_serving_cfg(True)
    cfg = dataclasses.replace(cfg, d_model=256, d_ff=1024, head_dim=64,
                              spls=dataclasses.replace(cfg.spls,
                                                       s_threshold=0.95))
    params = init_params(cfg, jax.random.PRNGKey(0))
    scfg = ServeConfig(n_slots=3, max_len=_PK_PROMPT + _PK_NEW + _PS,
                       page_size=_PS, prefill_chunk=_PK_CHUNK,
                       attn_backend="xla_paged_decode", spls_prune_vote=1.0,
                       compute_backend=compute_backend, capacity_margin=1.0,
                       vote_horizon=vote_horizon)
    eng = PagedServingEngine(cfg, params, scfg)

    def batch(rid0, n, max_new):
        reqs = [Request(rid=rid0 + i, prompt=jax.random.randint(
            jax.random.PRNGKey(300 + rid0 + i), (_PK_PROMPT,),
            0, cfg.vocab_size), max_new_tokens=max_new) for i in range(n)]
        for r in reqs:
            eng.submit(r)
        return reqs

    # warmup: converge the capacity controller's EMA and compile the
    # bucket variants it settles on (16 chunks; a residual one-off
    # compile in the timed window stays possible if the estimate crosses
    # a bucket boundary mid-measurement, but the dense baseline has one
    # variant and the same exposure to first-call compiles)
    batch(900, 4, 1)
    eng.run_until_drained(max_ticks=2000)
    reqs = batch(0, _PK_REQS, _PK_NEW)
    t0 = time.perf_counter()
    eng.run_until_drained(max_ticks=2000)
    dt = time.perf_counter() - t0
    assert all(r.done for r in reqs)
    tokens = sum(len(r.output) for r in reqs)
    saved = eng.stats["flops_saved_pct"]
    return dt * 1e6, {"tok_s": round(tokens / dt, 1),
                      "flops_saved_qkv_pct": round(saved["qkv"], 1),
                      "flops_saved_attn_pct": round(saved["attn"], 1),
                      "flops_saved_ffn_pct": round(saved["ffn"], 1),
                      "flops_saved_kv_pct": round(saved.get("kv", 0.0), 1)
                      }, eng, dt


def run():
    rows = []
    # BERT-Base @ L=512 (the paper's calibration workload is L=128 D=768)
    for L in (128, 512):
        sb = speedup_breakdown(L, 768, 12, 3072, PAPER_REDUCTIONS)
        rows.append((f"throughput/breakdown_L{L}", 0.0, {
            "spls_x": round(sb["spls_speedup"], 3),
            "progressive_x": round(sb["progressive_speedup"], 3),
            "dynamic_x": round(sb["dynamic_speedup"], 3),
            "end_to_end_x": round(sb["end_to_end_speedup"], 3)}))
    rows.append(("throughput/paper_reference", 0.0, {
        "spls_x": 1.59, "progressive_x": 1.18, "dynamic_x": 1.04,
        "asic_vs_v100_x": 2.42, "end_to_end_vs_v100_x": 4.72}))

    ee = energy_efficiency(512, 768, 12, 3072, PAPER_REDUCTIONS)
    rows.append(("energy/end_to_end", 0.0,
                 {k: round(v, 3) for k, v in ee.items()}))
    rows.append(("energy/paper_reference", 0.0, {"tops_per_w": 3.27}))

    ac = attention_level_comparison(512, 768, 12,
                                    PAPER_REDUCTIONS["attention"])
    rows.append(("energy/attention_level", 0.0,
                 {k: round(v, 3) for k, v in ac.items()}))
    rows.append(("energy/attention_paper_reference", 0.0, {
        "energy_eff_gops_w": 6677, "vs_spatten": 2.95, "vs_sanger": 2.26}))

    # measured serving: paged pool vs paged+SPLS pruning,
    # plus the long-prompt chunked-prefill pair (dense chunked vs the
    # progressive chunked+SPLS path -- the acceptance comparison)
    derived = {}
    outputs = {}
    for mode in ("paged", "paged_spls", "paged_chunked",
                 "paged_spls_chunked"):
        us, d, _eng, outs = _measure_engine(mode)
        derived[mode] = d
        outputs[mode] = outs
        rows.append((f"serving/{mode}", round(us, 1), d))

    # telemetry overhead: the same progressive-SPLS workload with the
    # no-op sink.  Greedy outputs must match bit-for-bit (telemetry is
    # host-side only; the acceptance invariant).  The main-loop on-run
    # above paid this mode's first-call jit compiles inside its timed
    # window, so compare a matched warm pair instead: off then on again,
    # both reusing the now-populated jit cache (CPU smoke scale is
    # dispatch-dominated, so the delta bounds the TPU overhead above)
    # best-of-2 per arm, alternating, to suppress CPU contention noise
    # (single pairs swing +-5% on a loaded host; the arms measure within
    # noise of each other when run in isolation)
    tok = {True: 0.0, False: 0.0}
    us_off = 0.0
    for arm in (False, True, False, True):
        us_arm, d_arm, _eng_arm, outs_arm = _measure_engine(
            "paged_spls_chunked", telemetry=arm)
        assert outs_arm == outputs["paged_spls_chunked"], \
            "telemetry changed greedy outputs"
        tok[arm] = max(tok[arm], d_arm["tok_s"])
        if not arm:
            us_off = us_arm
    tok_on = tok[True]
    tok_off = tok[False]
    rows.append(("serving/telemetry_overhead", round(us_off, 1), {
        "tok_s_telemetry_on": tok_on,
        "tok_s_telemetry_off": tok_off,
        "overhead_pct": round(100.0 * (1.0 - tok_on / max(tok_off, 1e-9)),
                              2),
        "outputs_bitwise_equal": True}))
    gain = (derived["paged_spls"]["req_per_mb"]
            / max(derived["paged"]["req_per_mb"], 1e-9))
    rows.append(("serving/summary", 0.0, {
        "req_per_mb_paged": derived["paged"]["req_per_mb"],
        "req_per_mb_paged_spls": derived["paged_spls"]["req_per_mb"],
        "paged_spls_vs_paged_x": round(gain, 2)}))
    ck, cs = derived["paged_chunked"], derived["paged_spls_chunked"]
    rows.append(("serving/summary_chunked", 0.0, {
        "peak_pages_dense_chunked": ck["pages_in_use_peak"],
        "peak_pages_spls_chunked": cs["pages_in_use_peak"],
        "page_reduction_x": round(ck["pages_in_use_peak"]
                                  / max(cs["pages_in_use_peak"], 1), 2),
        "req_per_mb_dense_chunked": ck["req_per_mb"],
        "req_per_mb_spls_chunked": cs["req_per_mb"],
        "tok_s_dense_chunked": ck["tok_s"],
        "tok_s_spls_chunked": cs["tok_s"]}))

    # end-to-end sparse prefill: same chunked+SPLS engine, dense compute
    # vs packed compute (token-compacted QKV/attention/FFN); the packed
    # row must win tok/s with nonzero qkv AND ffn savings.  The
    # vote_horizon=1 row adds horizon-finalized column votes: the only
    # row where the K/V projection itself runs packed (nonzero
    # flops_saved_kv_pct -- the acceptance metric for the early vote)
    pk = {}
    report_src = None
    for cb, h in (("dense", None), ("packed_xla", None), ("packed_xla", 1)):
        us, d, eng, dt = _measure_packed_prefill(cb, vote_horizon=h)
        tag = cb if h is None else f"{cb}_h{h}"
        pk[tag] = d
        if tag == "packed_xla_h1":
            report_src = (eng, dt)
        rows.append((f"serving/prefill_compute_{tag}", round(us, 1), d))
    rows.append(("serving/summary_packed_prefill", 0.0, {
        "tok_s_dense_compute": pk["dense"]["tok_s"],
        "tok_s_packed_xla": pk["packed_xla"]["tok_s"],
        "packed_vs_dense_x": round(pk["packed_xla"]["tok_s"]
                                   / max(pk["dense"]["tok_s"], 1e-9), 2),
        "flops_saved_qkv_pct": pk["packed_xla"]["flops_saved_qkv_pct"],
        "flops_saved_attn_pct": pk["packed_xla"]["flops_saved_attn_pct"],
        "flops_saved_ffn_pct": pk["packed_xla"]["flops_saved_ffn_pct"],
        "flops_saved_kv_pct_h1": pk["packed_xla_h1"]["flops_saved_kv_pct"],
        "tok_s_packed_xla_h1": pk["packed_xla_h1"]["tok_s"]}))

    # BENCH_serving.json: the schema-versioned serving trajectory
    # artifact (ROADMAP item 5), built from the vote_horizon=1 packed
    # run's telemetry -- the richest row (TTFT/TPOT percentiles, all
    # four flops_saved components, capacity occupancy, pool bytes) --
    # and written to the repo root on every benchmark run
    from pathlib import Path

    from repro.observability import (serving_report, validate_report,
                                     write_report)

    eng, dt = report_src
    # wall_s defaults to time-since-engine-start so throughput covers the
    # same window the telemetry's request records cover (incl. warmup)
    report = serving_report(eng, extra={
        "workload": {"bench": "throughput/packed_prefill_h1",
                     "prompt_len": _PK_PROMPT, "chunk": _PK_CHUNK,
                     "n_requests": _PK_REQS, "max_new": _PK_NEW},
        "telemetry_overhead_pct": rows and next(
            (r[2]["overhead_pct"] for r in rows
             if r[0] == "serving/telemetry_overhead"), None)})
    validate_report(report)
    path = Path(__file__).resolve().parents[1] / "BENCH_serving.json"
    write_report(str(path), report)
    rows.append(("serving/bench_json", 0.0, {
        "path": str(path), "schema_version": report["schema_version"],
        "ttft_p50_ms": report["latency"]["ttft_ms"]["p50"],
        "tpot_p50_ms": report["latency"]["tpot_ms"]["p50"]}))
    return rows
