"""Unified SPLS planner (repro.core.planner): driver-unification parity,
horizon-finalized column votes (None == end-of-prefill bit-for-bit, finite
horizons monotone), the int8 predictor-cache round-trip, packed K/V
projection parity, and whole-prompt packed routing."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, BlockCfg
from repro.core.planner import (PlanContext, horizon_update_live,
                                own_column_keep, pack_within_capacity,
                                votes_from_kv_any)
from repro.core.spls import SPLSConfig
from repro.core.spls_chunked import chunked_plan_scan
from repro.core.topk import topk_count
from repro.models import init_params
from repro.serving import (PagedServingEngine, Request, ServeConfig,
                           init_pred_cache, spls_token_votes)
from repro.serving.pager import keep_from_votes

jax.config.update("jax_platform_name", "cpu")

_PARAMS_CACHE = {}


def _cfg(**kw):
    base = dict(name="tiny-planner", n_layers=2, d_model=32, n_heads=4,
                n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=64,
                period=(BlockCfg(),), remat=False)
    base.update(kw)
    return ArchConfig(**base)


def _spls_cfg(**kw):
    spls = dict(enabled=True, k_ratio=0.12, s_threshold=0.6, f_threshold=2,
                window=4, causal=True)
    spls.update(kw.pop("spls_kw", {}))
    return _cfg(spls=SPLSConfig(**spls), **kw)


def _params(cfg):
    key = (cfg.name, cfg.period, cfg.spls.enabled, cfg.spls.k_ratio)
    if key not in _PARAMS_CACHE:
        _PARAMS_CACHE[key] = init_params(cfg, jax.random.PRNGKey(0))
    return _PARAMS_CACHE[key]


def _blk0(cfg, params):
    return jax.tree.map(lambda a: a[0], params["periods"][0])


def _reqs(cfg, lens, max_new=4, seed0=10):
    return [Request(rid=i, prompt=jax.random.randint(
        jax.random.PRNGKey(seed0 + i), (lp,), 0, cfg.vocab_size),
        max_new_tokens=max_new) for i, lp in enumerate(lens)]


def _drain(eng, reqs):
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained(max_ticks=3000)
    assert all(r.done for r in reqs)
    return [r.output for r in reqs]


# ---------------------------------------------------------------------------
# driver unification: identical plans from identical predicted heads
# ---------------------------------------------------------------------------

class TestDriverParity:
    def _heads(self, B=1, KV=2, G=2, L=32, Dh=16, seed=0):
        qh = jax.random.normal(jax.random.PRNGKey(seed), (B, KV, G, L, Dh))
        kh = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, KV, L, Dh))
        return qh, kh

    def test_three_drivers_identical_plans(self):
        """One-shot (simulation), lax.scan (progressive), and streaming
        chunk-by-chunk (serving) emit identical plans on identical
        predicted heads -- the planner-unification invariant."""
        L, C = 32, 8
        cfg = _spls_cfg()
        ctx = PlanContext.for_config(cfg, mode="structured")
        qh, kh = self._heads(L=L)
        k = topk_count(L, cfg.spls.k_ratio)

        one = ctx.plan_block(qh, kh, k=k, row0=0, n_valid_rows=L, n_cols=L)

        scan = chunked_plan_scan(
            qh, kh, k_ratio=cfg.spls.k_ratio,
            s_threshold=cfg.spls.s_threshold, window=cfg.spls.window,
            f_threshold=cfg.spls.f_threshold, row_block=C)
        np.testing.assert_array_equal(np.asarray(scan.q_critical),
                                      np.asarray(one.q_critical))
        np.testing.assert_array_equal(np.asarray(scan.q_leader),
                                      np.asarray(one.q_leader))
        np.testing.assert_array_equal(np.asarray(scan.kv_keep),
                                      np.asarray(one.kv_any))
        np.testing.assert_array_equal(np.asarray(scan.ffn_critical),
                                      np.asarray(one.ffn_critical))

        # streaming: grow the column buffer chunk by chunk, votes OR'd
        acc = None
        got_crit, got_lead = [], []
        for c0 in range(0, L, C):
            seen = c0 + C
            kh_buf = jnp.concatenate(
                [kh[:, :, :seen], jnp.full((1, 2, L - seen, 16), 7.0)],
                axis=2)  # garbage past the seen columns
            pb = ctx.plan_block(qh[..., c0:c0 + C, :], kh_buf, k=k, row0=c0,
                                n_valid_rows=C, n_cols=seen)
            acc = pb.kv_any if acc is None else acc | pb.kv_any
            got_crit.append(pb.q_critical)
            got_lead.append(pb.q_leader)
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(a) for a in got_crit], -1),
            np.asarray(one.q_critical))
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(a) for a in got_lead], -1),
            np.asarray(one.q_leader))
        np.testing.assert_array_equal(np.asarray(acc), np.asarray(one.kv_any))

    def test_progressive_assembly_matches_vote_iter(self):
        """plan_progressive's kv_keep equals the OR of the votes-only
        block iterator -- full plans and the serving vote path share one
        block source."""
        cfg = _spls_cfg()
        params = _params(cfg)
        blk0 = _blk0(cfg, params)
        xn = jax.random.normal(jax.random.PRNGKey(3), (1, 24, cfg.d_model))
        ctx = PlanContext.for_config(cfg)
        plan = ctx.plan_progressive(blk0["attn"], xn, row_block=8)
        acc = None
        for v in ctx.iter_blocks(blk0["attn"], xn, row_block=8,
                                 votes_only=True):
            acc = v if acc is None else acc | v
        np.testing.assert_array_equal(np.asarray(plan.kv_keep),
                                      np.asarray(acc))

    def test_col_live_kills_columns(self):
        """Dead columns (col_live False) can neither win top-k mask bits
        nor receive keep votes."""
        cfg = _spls_cfg()
        ctx = PlanContext.for_config(cfg, mode="structured")
        qh, kh = self._heads(L=16)
        live = jnp.ones((16,), bool).at[5].set(False).at[11].set(False)
        pb = ctx.plan_block(qh, kh, k=jnp.int32(4), row0=0, n_valid_rows=16,
                            n_cols=16, col_live=live)
        m = np.asarray(pb.mask)
        assert not m[..., 5].any() and not m[..., 11].any()
        v = np.asarray(pb.kv_any)
        assert not v[..., 5].any() and not v[..., 11].any()


# ---------------------------------------------------------------------------
# int8 predictor-cache codes
# ---------------------------------------------------------------------------

class TestPredCacheCodes:
    @pytest.mark.parametrize("method", ["hlog", "hlog_bitlevel", "pot",
                                        "none"])
    def test_roundtrip_bitwise(self, method):
        """encode -> int8 codes + scale -> decode reproduces the
        dequantized predicted K bit-for-bit for every quantizer."""
        from repro.core.predict import predict_qk
        cfg = _spls_cfg(spls_kw=dict(quant_method=method))
        params = _params(_spls_cfg())  # weights independent of method
        blk0 = _blk0(cfg, params)
        xn = jax.random.normal(jax.random.PRNGKey(5), (1, 16, cfg.d_model))
        ctx = PlanContext.for_config(cfg, mode="structured")
        qh, codes, scale = ctx.encode_pred_qk(blk0["attn"], xn)
        assert codes.dtype == jnp.int8
        dec = ctx.decode_pred_k(codes, scale)
        D, KV, Dh = cfg.d_model, cfg.n_kv_heads, cfg.resolved_head_dim
        wq = blk0["attn"]["wq"].reshape(D, -1)
        wk = blk0["attn"]["wk"].reshape(D, KV * Dh)
        _, kp = predict_qk(xn, wq, wk, method, cfg.spls.quant_bits,
                           act_axis=-1)
        kp_h = kp.reshape(16, KV, Dh).transpose(1, 0, 2)
        np.testing.assert_array_equal(np.asarray(dec), np.asarray(kp_h))

    def test_roundtrip_bitwise_bf16(self):
        """Under bfloat16 compute the decode must multiply in bf16 (the
        dtype the old float cache stored): levels and the widened scale
        round-trip exactly, so decode(dtype=bf16) equals the bf16
        predict_qk output bit for bit (an f32 multiply would differ in
        the last ulp and flip marginal top-k columns)."""
        from repro.core.predict import predict_qk
        cfg = _spls_cfg()
        params = _params(cfg)
        blk0 = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                            _blk0(cfg, params))
        xn = jax.random.normal(jax.random.PRNGKey(6),
                               (1, 16, cfg.d_model)).astype(jnp.bfloat16)
        ctx = PlanContext.for_config(cfg, mode="structured")
        _, codes, scale = ctx.encode_pred_qk(blk0["attn"], xn)
        dec = ctx.decode_pred_k(codes, scale, dtype=jnp.bfloat16)
        assert dec.dtype == jnp.bfloat16
        D, KV, Dh = cfg.d_model, cfg.n_kv_heads, cfg.resolved_head_dim
        wq = blk0["attn"]["wq"].reshape(D, -1)
        wk = blk0["attn"]["wk"].reshape(D, KV * Dh)
        _, kp = predict_qk(xn, wq, wk, cfg.spls.quant_method,
                           cfg.spls.quant_bits, act_axis=-1)
        kp_h = kp.reshape(16, KV, Dh).transpose(1, 0, 2)
        np.testing.assert_array_equal(
            np.asarray(dec, np.float32), np.asarray(kp_h, np.float32))

    def test_pool_bytes_reduced(self):
        """The paged predictor cache charges int8 codes + one float32
        scale per slot -- strictly below the old float32-value layout."""
        cfg = _spls_cfg()
        pred = init_pred_cache(cfg, n_pages=8, page_size=4)
        got = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pred))
        KV, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
        n_blocks = len(cfg.period)
        old = n_blocks * cfg.n_periods * KV * 8 * 4 * Dh * 4  # float32
        assert got < old / 2, (got, old)
        assert pred[0].codes.dtype == jnp.int8
        assert pred[0].scale.dtype == jnp.float32

    def test_wide_quant_bits_rejected(self):
        cfg = _spls_cfg(spls_kw=dict(quant_bits=16))
        with pytest.raises(ValueError, match="quant_bits"):
            init_pred_cache(cfg, n_pages=4, page_size=4)


# ---------------------------------------------------------------------------
# horizon-finalized column votes
# ---------------------------------------------------------------------------

class _KeepRecorder(PagedServingEngine):
    """Records each sequence's final keep set at compaction time."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.kept = {}

    def _finish_chunk_prune(self, st):
        lp = st.prompt_len
        votes = st.head_votes.sum(axis=0).astype(np.int32)
        keep = keep_from_votes(votes[:lp], self.cfg.n_heads,
                               self.scfg.spls_prune_vote)
        if st.live is not None:
            keep = keep & st.live[:lp]
        self.kept[st.req.rid] = keep.copy()
        super()._finish_chunk_prune(st)


class _VoteRecorder(PagedServingEngine):
    """Records each sequence's accumulated head votes at compaction."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.votes = {}

    def _finish_chunk_prune(self, st):
        self.votes[st.req.rid] = st.head_votes.copy()
        super()._finish_chunk_prune(st)


class TestVoteHorizon:
    def _run(self, cfg, params, engine_cls=PagedServingEngine, lens=(30, 25),
             chunk=8, max_new=4, **scfg_kw):
        scfg = ServeConfig(n_slots=2, max_len=64, page_size=4,
                           prefill_chunk=chunk,
                           attn_backend="xla_paged_decode", **scfg_kw)
        eng = engine_cls(cfg, params, scfg)
        outs = _drain(eng, _reqs(cfg, lens, max_new=max_new))
        return outs, eng

    @pytest.mark.parametrize("chunk,gqa,swa", [(8, False, False),
                                               (16, False, False),
                                               (8, True, False),
                                               (8, False, True)])
    def test_none_streaming_votes_equal_end_of_prefill(self, chunk, gqa,
                                                       swa):
        """vote_horizon=None: the chunk-streamed vote accumulator equals
        the whole-prompt planner vote bit-for-bit, across chunk sizes,
        GQA groupings, and sliding-window blocks."""
        kw = {}
        if gqa:
            kw = dict(n_heads=4, n_kv_heads=1, name="tiny-planner-gqa")
        if swa:
            kw = dict(period=(BlockCfg(window=6),), name="tiny-planner-swa")
        cfg = _spls_cfg(**kw)
        params = _params(cfg)
        lens = (30, 25)
        _, eng = self._run(cfg, params, engine_cls=_VoteRecorder, lens=lens,
                           chunk=chunk, vote_horizon=None)
        for rid, lp in enumerate(lens):
            want = np.asarray(spls_token_votes(
                cfg, params, jnp.asarray(_reqs(cfg, lens)[rid].prompt)))
            got = eng.votes[rid].sum(axis=0).astype(np.int32)[:lp]
            np.testing.assert_array_equal(got, want)

    def test_none_is_default_engine_bitwise(self):
        """Explicit vote_horizon=None greedy outputs are bit-for-bit the
        default (PR-4) engine's, dense and packed compute alike."""
        cfg = _spls_cfg()
        params = _params(cfg)
        for cb, kw in (("dense", {}), ("packed_xla",
                                       dict(capacity_buckets=(8,)))):
            base, _ = self._run(cfg, params, compute_backend=cb, **kw)
            expl, _ = self._run(cfg, params, compute_backend=cb,
                                vote_horizon=None, **kw)
            assert base == expl, cb

    def test_full_vote_horizon_one_is_lossless(self):
        """k_ratio=1.0 makes every column win the cross-head vote inside
        its own chunk, so vote_horizon=1 (packed K/V projection included)
        must reproduce vote_horizon=None bit-for-bit -- this pins the
        packed_project_kv numerics end to end."""
        cfg = _spls_cfg(spls_kw=dict(k_ratio=1.0), name="tiny-planner-k1")
        params = _params(cfg)
        a, _ = self._run(cfg, params, compute_backend="packed_xla",
                         capacity_buckets=(8,))
        b, eng = self._run(cfg, params, compute_backend="packed_xla",
                           capacity_buckets=(8,), vote_horizon=1)
        assert a == b
        assert eng.stats["capacity_kv"]["observations"] > 0

    def test_horizon_monotone_kept_columns(self):
        """Larger horizon => superset of kept columns (votes are monotone;
        a longer probation can only rescue columns)."""
        cfg = _spls_cfg(spls_kw=dict(s_threshold=0.9))
        params = _params(cfg)
        kept = {}
        for h in (1, 2, 4, None):
            _, eng = self._run(cfg, params, engine_cls=_KeepRecorder,
                               lens=(30, 30, 25), chunk=8,
                               compute_backend="packed_xla",
                               capacity_buckets=(8,), vote_horizon=h)
            kept[h] = eng.kept
        for a, b in ((1, 2), (2, 4), (4, None)):
            for rid in kept[a]:
                assert (~kept[a][rid] | kept[b][rid]).all(), (a, b, rid)

    def test_finite_horizon_prunes_and_drains(self):
        """A finite horizon with sparse votes finalizes columns early,
        the engine still drains, and the final keep honors liveness."""
        cfg = _spls_cfg(spls_kw=dict(s_threshold=0.9))
        params = _params(cfg)
        outs, eng = self._run(cfg, params, engine_cls=_KeepRecorder,
                              lens=(30, 25), vote_horizon=2)
        assert all(len(o) == 4 for o in outs)
        assert eng.stats["retired"] == 2

    def test_horizon_requires_spls_and_prune(self):
        cfg = _cfg()
        with pytest.raises(ValueError, match="vote_horizon"):
            PagedServingEngine(cfg, _params(cfg), ServeConfig(
                n_slots=2, max_len=64, page_size=4, vote_horizon=1))
        cfg = _spls_cfg()
        with pytest.raises(ValueError, match="vote_horizon"):
            PagedServingEngine(cfg, _params(cfg), ServeConfig(
                n_slots=2, max_len=64, page_size=4, vote_horizon=0))

    def test_host_mirror_matches_device_decision(self):
        """horizon_update_live's kv_capacity branch reproduces exactly the
        own_column_keep + pack_within_capacity decision the device
        materialized (anchor reservation included)."""
        rng = np.random.RandomState(0)
        CS, S, Ckv, last = 8, 32, 4, 29
        for start in (0, 8, 24):
            kv_any = rng.rand(1, 2, 2, S) < 0.3
            need = 2
            dev_keep = np.asarray(own_column_keep(
                jnp.asarray(kv_any), start=jnp.int32(start), chunk=CS,
                valid=jnp.int32(CS), last_keep=jnp.int32(last),
                vote_need=need))
            anchor = start + np.arange(CS) == last
            dev_written = np.asarray(pack_within_capacity(
                jnp.asarray(dev_keep), Ckv, anchor=jnp.asarray(anchor)))
            live = np.ones((S,), bool)
            counts = kv_any.reshape(-1, S).sum(axis=0).astype(np.int32)
            host = horizon_update_live(
                live, counts, start=start, valid=CS, chunk=CS, horizon=1,
                last_keep=last, vote_need=need, kv_capacity=Ckv)
            np.testing.assert_array_equal(host[start:start + CS],
                                          dev_written)

    def test_anchor_survives_capacity_overflow(self):
        """The decode anchor (highest index of its chunk) keeps its
        reserved projection slot even when the vote-surviving count
        overflows kv_capacity -- plain pack order would drop it first."""
        keep = jnp.ones((8,), bool)        # every column vote-kept
        anchor = jnp.arange(8) == 7        # anchor at the chunk's end
        w = np.asarray(pack_within_capacity(keep, 3, anchor=anchor))
        assert w[7]                        # reserved despite overflow
        assert w.sum() == 3                # capacity still respected
        np.testing.assert_array_equal(w[:7],
                                      [True, True, False, False, False,
                                       False, False])
        # without an anchor present the cap is the plain prefix rule
        w2 = np.asarray(pack_within_capacity(keep, 3,
                                             anchor=jnp.zeros(8, bool)))
        np.testing.assert_array_equal(
            w2, np.asarray(pack_within_capacity(keep, 3)))

    def test_anchor_survives_overflow_in_engine(self):
        """Engine-level regression: a pinned tiny kv capacity forces
        overflow on every chunk incl. the final one; the last prompt
        token's column must survive to anchor decode, and the engine must
        drain."""
        cfg = _spls_cfg(spls_kw=dict(k_ratio=1.0), name="tiny-planner-ovf")
        params = _params(cfg)
        scfg = ServeConfig(n_slots=2, max_len=64, page_size=4,
                           prefill_chunk=8,
                           attn_backend="xla_paged_decode",
                           compute_backend="packed_xla", vote_horizon=1)
        eng = _KeepRecorder(cfg, params, scfg)
        eng._cap_kv.capacity = lambda: 2   # force overflow every chunk
        outs = _drain(eng, _reqs(cfg, (30, 25)))
        assert all(len(o) == 4 for o in outs)
        assert eng.stats["capacity_kv"]["overflows"] > 0
        for rid in eng.kept:
            assert eng.kept[rid][-1]       # decode anchor kept


# ---------------------------------------------------------------------------
# packed K/V projection + whole-prompt routing
# ---------------------------------------------------------------------------

class TestPackedKV:
    @pytest.mark.parametrize("backend", ["packed_xla", "packed_pallas"])
    def test_packed_project_kv_bitwise(self, backend):
        """packed_project_kv slot c == row perm[c] of the dense
        project_kv output (XLA and Pallas-interpret), to float32
        summation order: the reference runs at "highest" matmul precision
        and a row subset of a dot need not sum in the full dot's order,
        so the bound is a few ulps of the D=32 contraction (D * eps ~
        4e-6 relative), far below any bf16 or wrong-row error."""
        from repro.models.attention import project_kv
        cfg = _spls_cfg()
        params = _params(cfg)
        blk0 = _blk0(cfg, params)
        p = jax.tree.map(lambda a: a.astype(jnp.float32), blk0["attn"])
        xn = jax.random.normal(jax.random.PRNGKey(7), (1, 16, cfg.d_model))
        positions = jnp.arange(16)[None, :]
        with jax.default_matmul_precision("highest"):
            kd, vd = project_kv(cfg, p, xn, positions, "structured")
        perm = jnp.asarray([3, 0, 7, 12, 12, 5], jnp.int32)
        kp, vp = project_kv(cfg, p, xn, positions, "structured", perm=perm,
                            compute_backend=backend)
        np.testing.assert_allclose(np.asarray(kp), np.asarray(kd[:, :, perm]),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(vp), np.asarray(vd[:, :, perm]),
                                   rtol=1e-5, atol=1e-5)

    def test_whole_prompt_packed_routing(self):
        """Short prompts (<= one chunk) under a packed compute backend
        take one chunk step each: packed savings accrue, and greedy
        outputs still match the dense-compute engine."""
        cfg = _spls_cfg(spls_kw=dict(s_threshold=0.95, window=8),
                        name="tiny-planner-wp")
        params = _params(cfg)
        lens = (8, 6, 8)  # all <= prefill_chunk
        scfg = dict(n_slots=3, max_len=64, page_size=4, prefill_chunk=8,
                    attn_backend="xla_paged_decode")
        dense = PagedServingEngine(cfg, params, ServeConfig(
            compute_backend="dense", **scfg))
        d_out = _drain(dense, _reqs(cfg, lens))
        packed = PagedServingEngine(cfg, params, ServeConfig(
            compute_backend="packed_xla", capacity_buckets=(8,), **scfg))
        p_out = _drain(packed, _reqs(cfg, lens))
        assert packed.stats["prefill_chunks"] == len(lens)
        assert p_out == d_out
        # adaptive buckets: short prompts accrue packed savings (run a
        # warmup batch so the controllers' EMAs leave the conservative
        # first pick, then measure)
        adaptive = PagedServingEngine(cfg, params, ServeConfig(
            compute_backend="packed_xla", capacity_buckets=(2, 4, 6, 8),
            capacity_margin=1.0, **scfg))
        _drain(adaptive, _reqs(cfg, lens, seed0=50))
        _drain(adaptive, _reqs(cfg, lens))
        assert adaptive.stats["flops_saved_pct"]["ffn"] > 0.0

    def test_double_buffered_gather_multi_tile(self):
        """The double-buffered per-row DMA gather stays bitwise equal to
        the XLA oracle across multiple row tiles (interpret mode).  The
        data are small integers, so every product and partial sum is
        exact and equality holds whatever order each dot sums in."""
        from repro.kernels.gathered_matmul import gathered_matmul
        x = jax.random.randint(jax.random.PRNGKey(11), (100, 32), -8,
                               9).astype(jnp.float32)
        w = jax.random.randint(jax.random.PRNGKey(12), (32, 48), -8,
                               9).astype(jnp.float32)
        perm = jax.random.randint(jax.random.PRNGKey(13), (70,), 0, 100)
        out = gathered_matmul(x, w, perm, bm=16, interpret=True)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(x[perm] @ w))
