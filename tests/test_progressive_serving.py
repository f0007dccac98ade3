"""Progressive SPLS for serving: streaming per-chunk plan construction,
chunked+SPLS prefill parity between one chunk and many, page-prune
vote accumulation, O(chunk * L) plan memory, the PagePool double-free guard,
the padded-chunk null-page sentinel, and backend-kind mismatch warnings."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.configs.base import ArchConfig, BlockCfg
from repro.core.spls import SPLSConfig
from repro.core.spls_chunked import plan_chunk, votes_from_kv_any
from repro.core.topk import topk_count
from repro.models import init_params, resolve_backend
from repro.models import attn_backend as ab
from repro.models.common import cast_compute, dtype_of
from repro.serving import (PagePool, PagedServingEngine, Request,
                           Scheduler, SchedulerConfig, ServeConfig,
                           SeqState, spls_token_votes)

from _reference import greedy_references

jax.config.update("jax_platform_name", "cpu")

_PARAMS_CACHE = {}


def _cfg(**kw):
    base = dict(name="tiny", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                head_dim=16, d_ff=64, vocab_size=64, period=(BlockCfg(),),
                remat=False)
    base.update(kw)
    return ArchConfig(**base)


def _spls_cfg(**kw):
    spls = dict(enabled=True, k_ratio=0.12, s_threshold=0.6, f_threshold=2,
                window=4, causal=True)
    spls.update(kw.pop("spls_kw", {}))
    return _cfg(name="tiny-spls-prog", spls=SPLSConfig(**spls), **kw)


def _params(cfg):
    key = (cfg.name, cfg.period, cfg.spls.enabled)
    if key not in _PARAMS_CACHE:
        _PARAMS_CACHE[key] = init_params(cfg, jax.random.PRNGKey(0))
    return _PARAMS_CACHE[key]


def _reqs(cfg, lens, max_new=5, seed0=0):
    return [Request(rid=i, prompt=jax.random.randint(
        jax.random.PRNGKey(seed0 + i), (lp,), 0, cfg.vocab_size),
        max_new_tokens=max_new) for i, lp in enumerate(lens)]


def _drain(engine, reqs):
    for r in reqs:
        engine.submit(r)
    engine.run_until_drained(max_ticks=3000)
    assert all(r.done for r in reqs)
    return [r.output for r in reqs]


# ---------------------------------------------------------------------------
# core: streaming plan blocks
# ---------------------------------------------------------------------------

class TestPlanChunkStreaming:
    def _heads(self, B=1, KV=2, G=2, L=32, Dh=16, seed=0):
        qh = jax.random.normal(jax.random.PRNGKey(seed), (B, KV, G, L, Dh))
        kh = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, KV, L, Dh))
        return qh, kh

    def test_streaming_equals_single_block(self):
        """Chunk-by-chunk plan blocks over a padded, progressively filled
        column buffer reproduce the single-block plan exactly -- including
        the accumulated column votes.  This is the invariant that makes
        chunked and full prefills agree."""
        L, S, C, w = 32, 48, 8, 4
        qh, kh = self._heads(L=L)
        k = topk_count(L, 0.2)
        kw = dict(k=k, s_threshold=0.7, window=w, f_threshold=2, causal=True)

        ref = plan_chunk(qh, kh, row0=0, n_valid_rows=L, n_cols=L, **kw)

        # streaming: the column buffer is larger than the prompt and only
        # filled up to the current chunk's end; the rest is garbage
        noise = jax.random.normal(jax.random.PRNGKey(9),
                                  (1, 2, S - L, 16)) * 100
        acc = None
        got = {f: [] for f in ("mask", "q_critical", "q_leader",
                               "ffn_critical", "ffn_leader")}
        for c0 in range(0, L, C):
            seen = c0 + C
            kh_buf = jnp.concatenate(
                [kh[:, :, :seen], jnp.zeros((1, 2, S - seen, 16))], axis=2)
            kh_buf = kh_buf.at[:, :, L:].set(noise)  # garbage past prompt
            pb = plan_chunk(qh[..., c0:c0 + C, :], kh_buf, row0=c0,
                            n_valid_rows=C, n_cols=seen, **kw)
            acc = pb.kv_any if acc is None else acc | pb.kv_any
            got["mask"].append(pb.mask[..., :L])
            got["q_critical"].append(pb.q_critical)
            got["q_leader"].append(pb.q_leader)
            got["ffn_critical"].append(pb.ffn_critical)
            got["ffn_leader"].append(pb.ffn_leader)

        for f in got:
            want = np.asarray(getattr(ref, f))
            have = np.concatenate([np.asarray(a) for a in got[f]], axis=-2
                                  if f == "mask" else -1)
            np.testing.assert_array_equal(have, want, err_msg=f)
        np.testing.assert_array_equal(
            np.asarray(votes_from_kv_any(acc))[:L],
            np.asarray(votes_from_kv_any(ref.kv_any)))

    def test_one_jit_covers_all_lengths(self):
        """k / row0 / valid counts are traced: a single compiled plan_chunk
        serves every prompt length (no per-length recompilation)."""
        qh, kh = self._heads(L=32)
        fn = jax.jit(lambda q, khh, k, r0, nv, nc: plan_chunk(
            q, khh, k=k, row0=r0, n_valid_rows=nv, n_cols=nc,
            s_threshold=0.7, window=4, f_threshold=2, causal=True))
        a = fn(qh[..., :8, :], kh, 4, 0, 8, 32)
        b = fn(qh[..., 8:16, :], kh, 7, 8, 6, 30)  # different scalars
        assert a.mask.shape == b.mask.shape
        assert fn._cache_size() == 1

    def test_votes_no_quadratic_intermediate(self):
        """The rerouted spls_token_votes never materializes an O(L^2)
        intermediate at an 8k prompt (jaxpr shape audit)."""
        cfg = _spls_cfg(spls_kw=dict(window=8))
        params = _params(cfg)
        Lp = 8192
        prompt = jax.ShapeDtypeStruct((Lp,), jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda p, t: spls_token_votes(cfg, p, t))(params, prompt)
        biggest = _max_aval_size(jaxpr.jaxpr)
        assert biggest < Lp * Lp, biggest  # dense plan would be H * L^2

    def test_chunk_step_no_quadratic_intermediate(self):
        """The per-chunk SPLS prefill step stays O(chunk * S) at an
        8k-slot table (jaxpr shape audit of the whole layer scan)."""
        from repro.serving import (init_paged_cache, init_pos_pages,
                                   init_pred_cache, paged_prefill_chunk_spls)
        cfg = _spls_cfg(spls_kw=dict(window=8))
        params = _params(cfg)
        ps, CS = 16, 64
        P = 512                      # 8192 slots
        n_pages = P + 1
        cache = jax.eval_shape(
            lambda: init_paged_cache(cfg, n_pages, ps))
        pred = jax.eval_shape(lambda: init_pred_cache(cfg, n_pages, ps))
        S = P * ps
        jaxpr = jax.make_jaxpr(
            lambda p, c, pc, pp, tb, s0, t, v, k: paged_prefill_chunk_spls(
                cfg, p, c, pc, pp, tb, s0, t, v, k))(
            cast_compute(params, dtype_of(cfg.compute_dtype)), cache, pred,
            jax.ShapeDtypeStruct((n_pages, ps), jnp.int32),
            jax.ShapeDtypeStruct((P,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((1, CS), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32))
        biggest = _max_aval_size(jaxpr.jaxpr)
        # O(CS * S) blocks are fine (largest: the windowed-L1 pairwise
        # tensor, heads * CS * window * S); O(S^2) is not
        assert biggest <= 64 * CS * S, biggest
        assert biggest < S * S, biggest


def _max_aval_size(jaxpr) -> int:
    best = 0
    for j in _iter_jaxprs(jaxpr):
        for eqn in j.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                size = getattr(aval, "size", 0)
                best = max(best, int(size))
    return best


def _iter_jaxprs(j):
    yield j
    for eqn in j.eqns:
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for u in vs:
                if isinstance(u, ClosedJaxpr):
                    yield from _iter_jaxprs(u.jaxpr)
                elif isinstance(u, Jaxpr):
                    yield from _iter_jaxprs(u)


# ---------------------------------------------------------------------------
# engine: chunked+SPLS prefill parity and page savings
# ---------------------------------------------------------------------------

class TestChunkedSplsServing:
    def _run(self, cfg, params, prefill_chunk, lens, *, prune=True,
             max_new=5, n_slots=3, max_len=64, page_size=4,
             backend="xla_paged_decode", vote=0.5):
        eng = PagedServingEngine(cfg, params, ServeConfig(
            n_slots=n_slots, max_len=max_len, page_size=page_size,
            prefill_chunk=prefill_chunk, attn_backend=backend,
            spls_page_prune=prune, spls_prune_vote=vote))
        outs = _drain(eng, _reqs(cfg, lens, max_new=max_new))
        return outs, eng

    @pytest.mark.parametrize("chunk", [8, 16])
    def test_parity_with_full_prefill_pruned(self, chunk):
        """Greedy outputs of chunked+SPLS prefill (pruning on) in many
        chunks match those of one whole-prompt chunk (64) bit-for-bit in
        the no-preemption regime, for multiple chunkings."""
        cfg = _spls_cfg()
        params = _params(cfg)
        lens = [30, 18, 25, 41]
        full, _ = self._run(cfg, params, prefill_chunk=64, lens=lens)
        chunked, eng = self._run(cfg, params, prefill_chunk=chunk,
                                 lens=lens)
        assert eng.stats["prefill_chunks"] >= sum(-(-l // chunk)
                                                  for l in lens)
        assert eng.stats["preemptions"] == 0
        assert full == chunked

    def test_parity_both_paged_backends(self):
        cfg = _spls_cfg()
        params = _params(cfg)
        outs = {}
        for be in ("xla_paged_decode", "pallas_paged_decode"):
            outs[be], _ = self._run(cfg, params, prefill_chunk=8,
                                    lens=[22, 13], backend=be)
        assert outs["xla_paged_decode"] == outs["pallas_paged_decode"]

    def test_no_prune_matches_dense_engine(self):
        """Chunked SPLS prefill with pruning *off* still executes the
        sparse (simulation-mode) compute -- outputs must equal the
        reference's, which prefills whole prompts."""
        cfg = _spls_cfg()
        params = _params(cfg)
        dense = greedy_references(
            cfg, params, _reqs(cfg, [27, 14, 33], max_new=4), max_len=64)
        chunked, _ = self._run(cfg, params, prefill_chunk=8,
                               lens=[27, 14, 33], prune=False, n_slots=2,
                               max_new=4)
        assert dense == chunked

    def test_sliding_window_chunked_spls(self):
        """SWA + chunked + SPLS: window masks evaluate original ids after
        padding and compaction; many chunks match one whole-prompt
        chunk."""
        cfg = _spls_cfg(period=(BlockCfg(window=6),))
        cfg = dataclasses.replace(cfg, name="tiny-spls-swa")
        params = _params(cfg)
        lens = [29, 17]
        full, _ = self._run(cfg, params, prefill_chunk=64, lens=lens)
        chunked, _ = self._run(cfg, params, prefill_chunk=8, lens=lens)
        assert full == chunked

    def test_chunked_spls_prunes_pages(self):
        """Peak pages with chunked+SPLS pruning land strictly below dense
        chunked prefill on the same workload."""
        cfg = _spls_cfg()
        params = _params(cfg)
        lens = [48, 40, 44]
        _, pruned = self._run(cfg, params, prefill_chunk=8, lens=lens,
                              max_len=80, vote=1.0)
        _, dense = self._run(cfg, params, prefill_chunk=8, lens=lens,
                             max_len=80, prune=False)
        assert pruned.stats["peak_pages"] < dense.stats["peak_pages"], \
            (pruned.stats, dense.stats)
        assert pruned.pool.free_pages == pruned.pool.capacity  # all freed

    def test_chunk_must_align_with_window(self):
        cfg = _spls_cfg()
        with pytest.raises(ValueError, match="window"):
            PagedServingEngine(cfg, _params(cfg), ServeConfig(
                n_slots=1, max_len=32, page_size=4, prefill_chunk=6))

    def test_preempted_chunked_spls_completes(self):
        """Preemption mid-prefill resets the vote accumulator with the
        SeqState; everything still drains (pruned continuations may differ
        under pool pressure -- documented determinism caveat)."""
        cfg = _spls_cfg()
        params = _params(cfg)
        eng = PagedServingEngine(cfg, params, ServeConfig(
            n_slots=3, max_len=48, page_size=4, n_pages=13,
            prefill_chunk=8, attn_backend="xla_paged_decode"))
        reqs = _reqs(cfg, [28, 28, 28], max_new=4)
        _drain(eng, reqs)
        assert eng.pool.free_pages == eng.pool.capacity


# ---------------------------------------------------------------------------
# scheduler: post-prune accounting + abort guard
# ---------------------------------------------------------------------------

class TestPruneAwareScheduling:
    def test_note_prune_ema_and_lifetime_estimate(self):
        pool = PagePool(20, 4)
        sched = Scheduler(SchedulerConfig(prefill_chunk=8), pool,
                          max_len=64, prune_aware=True)
        dense = sched.lifetime_pages(32, 16)      # no estimate yet
        assert dense == pool.pages_for(48)
        sched.note_prune(32, 8)                   # 25% kept
        est = sched.lifetime_pages(32, 16)
        # chunked prefill still peaks at the dense prompt; lifetime is
        # kept + budget
        assert est == max(pool.pages_for(32), pool.pages_for(8 + 16))
        assert est < dense
        sched.note_prune(32, 32)                  # ratio EMA moves up
        assert sched.prune_ratio == pytest.approx(0.625)

    def test_optimistic_submit_accepts_after_estimate(self):
        """A request dense accounting would reject is accepted once a
        prune estimate exists (post-prune footprint fits)."""
        pool = PagePool(12, 4)                    # 11 usable pages
        sched = Scheduler(SchedulerConfig(prefill_chunk=8), pool,
                          max_len=64, prune_aware=True)

        class R:
            rid = 0
        # dense: pages_for(40 + 16) = 14 > 11 -> reject
        with pytest.raises(ValueError):
            sched.submit(R(), list(range(40)), 16)
        sched.note_prune(40, 10)                  # 25% kept observed
        sched.submit(R(), list(range(40)), 16)    # now fits: 10 prefill,
        assert len(sched.waiting) == 1            # ~7 post-prune lifetime

    def test_solo_preemption_abort_guard(self):
        """A lone sequence that can never fit is aborted after
        max_solo_preemptions instead of relooping prefill forever."""
        pool = PagePool(4, 4)                     # 3 usable pages
        sched = Scheduler(SchedulerConfig(max_solo_preemptions=2), pool,
                          max_len=64, prune_aware=True)

        class R:
            rid, output, max_new_tokens = 7, [], 4
        req = R()
        for i in range(3):
            st = SeqState(req=req, base_prompt=[1], tokens=[1], budget=4,
                          slot=0, admit_seq=i)
            sched.slots[0] = st
            ok = sched.grow_to(st, 32)            # needs 8 > 3 pages
            assert not ok
        assert sched.stats["aborted"] == 1
        assert sched.aborted == [req]
        assert sched.stats["preemptions"] == 2
        # counter cleared on abort: a resubmitted rid starts fresh
        assert sched._solo_preempts == {}

    def test_solo_counter_resets_on_success(self):
        """A transient solo-preemption must not accumulate across separate
        pressure events once the sequence grows successfully."""
        pool = PagePool(6, 4)
        sched = Scheduler(SchedulerConfig(max_solo_preemptions=2), pool,
                          max_len=64, prune_aware=True)

        class R:
            rid, output, max_new_tokens = 3, [], 4
        st = SeqState(req=R(), base_prompt=[1], tokens=[1], budget=4,
                      slot=0, admit_seq=0)
        sched.slots[0] = st
        assert not sched.grow_to(st, 64)          # too big: solo-preempt
        assert sched._solo_preempts == {3: 1}
        sched.slots[0] = st
        assert sched.grow_to(st, 8)               # fits: counter resets
        assert sched._solo_preempts == {}


# ---------------------------------------------------------------------------
# PagePool double-free guard
# ---------------------------------------------------------------------------

class TestPagePoolGuard:
    def test_double_free_raises(self):
        pool = PagePool(6, 4)
        a = pool.alloc(2)
        pool.free(a)
        with pytest.raises(ValueError, match="double-free|not currently"):
            pool.free(a)
        assert pool.free_pages == 5               # no duplicate ids

    def test_foreign_and_null_page_free_raises(self):
        pool = PagePool(6, 4)
        with pytest.raises(ValueError):
            pool.free([99])
        with pytest.raises(ValueError, match="null"):
            pool.free([0])

    def test_free_list_never_duplicates(self):
        pool = PagePool(5, 4)
        a = pool.alloc(4)
        pool.free(a)
        try:
            pool.free(a[:1])
        except ValueError:
            pass
        got = pool.alloc(4)
        assert sorted(got) == sorted(a)           # each page exactly once


# ---------------------------------------------------------------------------
# padded chunk: null page stays inert
# ---------------------------------------------------------------------------

class TestPaddedChunkSentinel:
    def test_null_page_pos_sentinel_after_padded_chunk(self):
        from repro.serving import (NULL_PAGE, POS_SENTINEL,
                                   init_paged_cache, init_pos_pages,
                                   paged_prefill_chunk)
        cfg = _cfg()
        params = _params(cfg)
        ps, P = 4, 4
        cache = init_paged_cache(cfg, 6, ps)
        pos_pages = init_pos_pages(6, ps)
        table = jnp.asarray([1, 2, NULL_PAGE, NULL_PAGE], jnp.int32)
        toks = jnp.zeros((1, 8), jnp.int32)       # 8-row chunk, 5 valid
        _, cache, pos_pages = paged_prefill_chunk(
            cfg, cast_compute(params, dtype_of(cfg.compute_dtype)), cache,
            pos_pages, table,
            jnp.asarray(0, jnp.int32), toks, jnp.asarray(5, jnp.int32))
        # padded rows 5..7 all scatter to null-page slot 0: it must hold
        # the sentinel, not a real position id
        np.testing.assert_array_equal(np.asarray(pos_pages[NULL_PAGE]),
                                      np.full((ps,), POS_SENTINEL))

    def test_window_decode_ignores_null_page_after_padded_chunks(self):
        """Engine-level: sliding-window attention through ragged chunked
        prefill (every chunk but the first is padded) matches the
        reference -- null-page slots never win window mass."""
        cfg = _cfg(name="tiny-swa2", period=(BlockCfg(window=5),))
        params = _params(cfg)
        lens = [21, 9]                            # 21 -> chunks 8, 8, 5
        dense = greedy_references(cfg, params, _reqs(cfg, lens), max_len=40)
        eng = PagedServingEngine(cfg, params, ServeConfig(
            n_slots=2, max_len=40, page_size=4, prefill_chunk=8,
            attn_backend="xla_paged_decode"))
        paged = _drain(eng, _reqs(cfg, lens))
        assert dense == paged


# ---------------------------------------------------------------------------
# resolve_backend kind-mismatch diagnostics
# ---------------------------------------------------------------------------

class TestBackendKindMismatch:
    def setup_method(self):
        ab._warned_kind_mismatch.clear()

    def test_warns_and_falls_back(self):
        cfg = _cfg()
        with pytest.warns(RuntimeWarning, match=r"'xla_paged_decode'.*"
                          r"paged decode backend.*forward site"):
            name = resolve_backend("xla_paged_decode", cfg, L=64,
                                   platform="cpu")
        assert name == "xla_dense"                # the forward auto choice

    def test_warns_once_per_name_site(self):
        cfg = _cfg()
        with pytest.warns(RuntimeWarning):
            resolve_backend("xla_dense", cfg, L=64, decode=True,
                            platform="cpu")
        import warnings as w
        with w.catch_warnings():
            w.simplefilter("error")               # second call must be quiet
            got = resolve_backend("xla_dense", cfg, L=64, decode=True,
                                  platform="cpu")
        assert got == "xla_dense_decode"

    def test_strict_raises(self):
        cfg = _cfg()
        with pytest.raises(ValueError, match="forward site"):
            resolve_backend("xla_paged_decode", cfg, L=64, platform="cpu",
                            strict=True)
        ab.STRICT_BACKEND_KIND = True
        try:
            with pytest.raises(ValueError):
                resolve_backend("pallas_flash", cfg, L=64, decode=True,
                                platform="cpu")
        finally:
            ab.STRICT_BACKEND_KIND = False

    def test_engine_config_does_not_warn(self):
        """ServeConfig.attn_backend naming a paged decode backend is the
        engine's documented usage: the engine routes the name to its
        decode site -- no kind-mismatch warning, and STRICT_BACKEND_KIND
        stays usable."""
        import warnings as w
        cfg = _spls_cfg()
        params = _params(cfg)
        ab.STRICT_BACKEND_KIND = True
        try:
            with w.catch_warnings():
                w.simplefilter("error", RuntimeWarning)
                eng = PagedServingEngine(cfg, params, ServeConfig(
                    n_slots=1, max_len=48, page_size=4, prefill_chunk=8,
                    attn_backend="xla_paged_decode"))
                _drain(eng, _reqs(cfg, [12], max_new=2))
        finally:
            ab.STRICT_BACKEND_KIND = False

    def test_matching_kind_never_warns(self):
        import warnings as w
        cfg = _cfg()
        with w.catch_warnings():
            w.simplefilter("error")
            assert resolve_backend("xla_dense", cfg, L=64,
                                   platform="cpu") == "xla_dense"
            assert resolve_backend("xla_paged_decode", cfg, L=64,
                                   decode=True, paged=True,
                                   platform="cpu") == "xla_paged_decode"
