"""End-to-end sparse compute: the gathered-matmul kernel vs the XLA
pack/unpack oracle, plan->compaction adapters (incl. the capacity-overflow
window-leader fallback), packed Q/MLP parity with the dense projections,
the capacity controller, the compute-backend registry, and engine-level
bit-for-bit parity of packed serving prefill with the dense-compute
(simulation-mode) baseline at capacity == L."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, BlockCfg
from repro.core.sparse_exec import (compact_rows, gather_rows, spls_ffn,
                                    spls_ffn_packed)
from repro.core.spls import SparsityPlan, SPLSConfig
from repro.kernels.gathered_matmul import gather_rows_kernel, gathered_matmul
from repro.kernels.ref import gathered_matmul_ref
from repro.models import init_params
from repro.serving import PagedServingEngine, Request, ServeConfig
from repro.sparse_compute import (CapacityController, chunk_flops,
                                  available_compute_backends,
                                  packed_mlp, packed_project_q,
                                  resolve_compute_backend)

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
    return _cfg(name="tiny-spls-sc", spls=SPLSConfig(**spls), **kw)


def _params(cfg):
    key = (cfg.name, cfg.n_kv_heads, cfg.spls.enabled, cfg.qk_norm)
    if key not in _PARAMS_CACHE:
        _PARAMS_CACHE[key] = init_params(cfg, jax.random.PRNGKey(0))
    return _PARAMS_CACHE[key]


def _int_valued(key, shape):
    """Small-integer float32 data: every product and partial sum of the
    matmuls below is exact, so equality with the oracle holds whatever
    order a dot sums in (float data only agrees to summation order)."""
    return jax.random.randint(key, shape, -8, 9).astype(jnp.float32)


# ---------------------------------------------------------------------------
# kernel parity vs the XLA pack/unpack oracle
# ---------------------------------------------------------------------------

class TestGatheredMatmulKernel:
    @pytest.mark.parametrize("L,D,F,C", [
        (33, 48, 40, 5),      # ragged everything
        (64, 64, 48, 16),     # capacity bucket < L
        (16, 32, 8, 16),      # capacity == L
        (40, 16, 128, 64),    # C > L (repeated rows / filler slots)
    ])
    def test_matches_oracle_bitwise(self, L, D, F, C):
        x = _int_valued(jax.random.PRNGKey(0), (L, D))
        w = _int_valued(jax.random.PRNGKey(1), (D, F))
        perm = jax.random.randint(jax.random.PRNGKey(2), (C,), 0, L)
        out = gathered_matmul(x, w, perm, bm=8, bn=16)
        ref = gathered_matmul_ref(x, w, perm)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_fused_scatter_matches_oracle(self):
        L, D, F, C, M = 32, 48, 24, 12, 50
        x = _int_valued(jax.random.PRNGKey(3), (L, D))
        w = _int_valued(jax.random.PRNGKey(4), (D, F))
        perm = jax.random.randint(jax.random.PRNGKey(5), (C,), 0, L)
        slot = jax.random.randint(jax.random.PRNGKey(6), (M,), 0, C)
        out = gathered_matmul(x, w, perm, src_slot=slot, bm=4, bn=8)
        ref = gathered_matmul_ref(x, w, perm, src_slot=slot)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_blocked_contraction_close(self):
        """bk < D trades the bitwise guarantee for VMEM (documented);
        results stay allclose."""
        x = jax.random.normal(jax.random.PRNGKey(7), (32, 64), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(8), (64, 32), jnp.float32)
        perm = jnp.arange(10, dtype=jnp.int32)
        out = gathered_matmul(x, w, perm, bm=4, bn=16, bk=16)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(gathered_matmul_ref(x, w, perm)),
                                   atol=2e-5)

    def test_gather_rows_kernel(self):
        src = jax.random.normal(jax.random.PRNGKey(9), (12, 40))
        idx = jax.random.randint(jax.random.PRNGKey(10), (30,), 0, 12)
        np.testing.assert_array_equal(
            np.asarray(gather_rows_kernel(src, idx)), np.asarray(src[idx]))


# ---------------------------------------------------------------------------
# plan -> compaction adapter (incl. the overflow window-leader fallback)
# ---------------------------------------------------------------------------

class TestCompactRows:
    def test_full_capacity_identity(self):
        crit = jnp.asarray([[1, 0, 1, 0, 0, 1, 0, 0]], bool)
        lead = jnp.asarray([[0, 0, 2, 2, 2, 5, 5, 5]], jnp.int32)
        c = compact_rows(crit, 8, leader=lead, window=4)
        # every row reads its leader's slot; leaders read their own
        perm = np.asarray(c.perm)[0]
        slot = np.asarray(c.src_slot)[0]
        for r in range(8):
            assert perm[slot[r]] == int(lead[0, r])
        assert int(c.n_critical[0]) == 3

    def test_overflow_falls_back_to_window_leader(self):
        """Rows whose leader overflowed capacity read the first *packed*
        critical row of their window -- not the legacy last-slot clamp."""
        # window 4: rows 0..3 critical 0, 2; rows 4..7 critical 4, 5, 6
        crit = jnp.asarray([[1, 0, 1, 0, 1, 1, 1, 0]], bool)
        lead = jnp.asarray([[0, 0, 2, 2, 4, 5, 6, 6]], jnp.int32)
        # capacity 3 packs critical rows 0, 2, 4; rows 5, 6 overflow
        c = compact_rows(crit, 3, leader=lead, window=4)
        perm = np.asarray(c.perm)[0]
        slot = np.asarray(c.src_slot)[0]
        assert list(perm) == [0, 2, 4]
        assert perm[slot[5]] == 4        # window leader of rows 4..7
        assert perm[slot[6]] == 4
        assert perm[slot[7]] == 4        # follower of overflow leader 6
        # non-overflow rows untouched
        assert perm[slot[0]] == 0 and perm[slot[2]] == 2
        assert perm[slot[3]] == 2 and perm[slot[4]] == 4

    def test_overflowed_window_leader_clamps(self):
        """If even the window leader overflowed, the legacy clamp (last
        packed slot) is the final fallback."""
        crit = jnp.asarray([[1, 1, 0, 0, 1, 1, 0, 0]], bool)
        lead = jnp.asarray([[0, 1, 1, 0, 4, 5, 5, 4]], jnp.int32)
        c = compact_rows(crit, 2, leader=lead, window=4)   # packs 0, 1
        perm = np.asarray(c.perm)[0]
        slot = np.asarray(c.src_slot)[0]
        # window [4..7]'s leader (row 4) overflowed -> clamp to slot C-1
        for r in (4, 5, 6, 7):
            assert slot[r] == 1

    def test_extra_head_dims_broadcast(self):
        """Per-head leaders over a shared (cross-head union) pack."""
        crit = jnp.asarray([[1, 1, 0, 1]], bool)              # (1, 4)
        lead = jnp.asarray([[[[0, 0, 1, 3]], [[1, 1, 0, 3]]]],
                           jnp.int32)                          # (1, 2, 1, 4)
        c = compact_rows(crit, 4, leader=lead, window=4)
        perm = np.asarray(c.perm)[0]
        slot = np.asarray(c.src_slot)[0]
        assert perm[slot[0, 0, 2]] == 1 and perm[slot[1, 0, 2]] == 0


class TestSplsFfnPackedOverflow:
    """Satellite: spls_ffn_packed vs spls_ffn below capacity -- overflow
    rows must fall back to their window leader's output exactly."""

    def _plan(self, crit, lead, L):
        B = crit.shape[0]
        z = jnp.zeros((B, 1, L), bool)
        return SparsityPlan(
            attn_mask=jnp.zeros((B, 1, L, L), bool), q_critical=z,
            q_leader=jnp.zeros((B, 1, L), jnp.int32),
            kv_keep=z, ffn_critical=crit, ffn_leader=lead)

    def test_overflow_rows_read_window_leader_exactly(self):
        L, D, w = 8, 16, 4
        x = jax.random.normal(jax.random.PRNGKey(0), (1, L, D))
        ffn = lambda t: jnp.tanh(t @ jax.random.normal(
            jax.random.PRNGKey(1), (D, D)))
        crit = jnp.asarray([[1, 0, 1, 0, 1, 1, 1, 0]], bool)
        lead = jnp.asarray([[0, 0, 2, 2, 4, 5, 6, 6]], jnp.int32)
        plan = self._plan(crit, lead, L)
        dense = ffn(x)                               # per-row ground truth
        out = spls_ffn_packed(x, ffn, plan, 3, window=w)
        out = np.asarray(out)
        # packed rows + their followers: exact leader outputs
        for r, ld in ((0, 0), (1, 0), (2, 2), (3, 2), (4, 4)):
            np.testing.assert_array_equal(out[0, r],
                                          np.asarray(dense[0, ld]))
        # overflow rows 5, 6 (and follower 7): window leader 4's output
        for r in (5, 6, 7):
            np.testing.assert_array_equal(out[0, r],
                                          np.asarray(dense[0, 4]))

    def test_full_capacity_equals_simulation(self):
        L, D = 16, 8
        x = jax.random.normal(jax.random.PRNGKey(2), (1, L, D))
        ffn = lambda t: t * 2.0 + 1.0
        crit = jnp.asarray([[1, 0, 0, 1] * 4], bool)
        lead = jnp.asarray([[0, 0, 0, 3, 4, 4, 4, 7,
                             8, 8, 8, 11, 12, 12, 12, 15]], jnp.int32)
        lead = jnp.where(crit, jnp.arange(L), lead).astype(jnp.int32)
        plan = self._plan(crit, lead, L)
        np.testing.assert_array_equal(
            np.asarray(spls_ffn_packed(x, ffn, plan, L, window=4)),
            np.asarray(spls_ffn(x, ffn, plan)))


# ---------------------------------------------------------------------------
# packed projections vs the dense model path
# ---------------------------------------------------------------------------

class TestPackedOps:
    @pytest.mark.parametrize("kv,heads", [(2, 4), (4, 4), (1, 4)])
    @pytest.mark.parametrize("backend", ["packed_xla", "packed_pallas"])
    def test_packed_project_q_bitwise(self, kv, heads, backend):
        """GQA head counts: packed Q rows == dense project_qkv rows, to
        float32 summation order.  The reference runs at "highest" matmul
        precision; a row subset of a dot need not sum in the same order
        as the full dot, so the bound is a few ulps of the D=32
        contraction (D * eps ~ 4e-6 relative), far below any bf16 or
        wrong-row error."""
        from repro.models.attention import project_qkv

        cfg = _spls_cfg(n_heads=heads, n_kv_heads=kv, qk_norm=True)
        p = jax.tree.map(lambda a: a[0],
                         _params(cfg)["periods"][0])["attn"]
        L, C = 16, 6
        xn = jax.random.normal(jax.random.PRNGKey(3), (1, L, cfg.d_model))
        positions = jnp.arange(10, 10 + L, dtype=jnp.int32)
        perm = jnp.asarray([0, 3, 7, 8, 12, 15], jnp.int32)
        with jax.default_matmul_precision("highest"):
            q_full, _, _ = project_qkv(cfg, p, xn, positions[None, :],
                                       "structured")
        want = np.asarray(gather_rows(q_full, jnp.broadcast_to(
            perm, (1, kv, heads // kv, C))))
        got = np.asarray(packed_project_q(cfg, p, xn, positions, perm,
                                          backend))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("backend", ["packed_xla", "packed_pallas"])
    @pytest.mark.parametrize("B", [1, 2])
    def test_packed_mlp_full_capacity_bitwise(self, backend, B):
        from repro.models.moe import mlp_forward

        cfg = _spls_cfg()
        p = jax.tree.map(lambda a: a[0],
                         _params(cfg)["periods"][0])["ffn"]
        L = 8
        x = jax.random.normal(jax.random.PRNGKey(4), (B, L, cfg.d_model))
        crit = jnp.tile(jnp.asarray([[1, 0, 1, 0, 1, 1, 0, 0]], bool),
                        (B, 1))
        lead = jnp.tile(jnp.asarray([[0, 0, 2, 2, 4, 5, 5, 4]], jnp.int32),
                        (B, 1))
        comp = compact_rows(crit, L, leader=lead, window=4)
        got = np.asarray(packed_mlp(cfg, p, x, comp, backend))
        dense = mlp_forward(cfg, p, x)
        want = np.asarray(gather_rows(dense, lead))
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# capacity controller + registry + accounting
# ---------------------------------------------------------------------------

class TestCapacityController:
    def test_conservative_until_observed(self):
        cc = CapacityController(64)
        assert cc.capacity() == 64

    def test_buckets_and_margin(self):
        cc = CapacityController(64, margin=1.0)
        assert cc.buckets == (16, 32, 48, 64)
        cc.observe(10)
        assert cc.capacity() == 16
        for _ in range(8):
            cc.observe(40)          # EMA climbs -> larger bucket
        assert cc.capacity() == 48
        assert cc.stats["observations"] == 9

    def test_custom_buckets_always_include_total(self):
        cc = CapacityController(64, buckets=(8, 200))
        assert cc.buckets == (8, 64)

    def test_margin_overshoot_clamps_to_total(self):
        cc = CapacityController(16, margin=4.0)
        cc.observe(15)
        assert cc.capacity() == 16


class TestRegistryAndAccounting:
    def test_registry_names(self):
        assert available_compute_backends() == ("dense", "packed_pallas",
                                                "packed_xla")

    def test_resolve(self):
        assert resolve_compute_backend(None, sparse=False) == "dense"
        assert resolve_compute_backend("auto", sparse=True,
                                       platform="cpu") == "packed_xla"
        assert resolve_compute_backend("auto", sparse=True,
                                       platform="tpu") == "packed_pallas"
        with pytest.raises(ValueError, match="spls.enabled"):
            resolve_compute_backend("packed_xla", sparse=False)
        with pytest.raises(ValueError, match="unknown compute backend"):
            resolve_compute_backend("nope", sparse=True)

    def test_chunk_flops_components(self):
        cfg = _spls_cfg()
        full = chunk_flops(cfg, 16, 32)
        packed = chunk_flops(cfg, 16, 32, q_rows=8, ffn_rows=4)
        for c in ("qkv", "attn", "ffn"):
            assert full[c][0] == full[c][1] > 0
            assert packed[c][1] < packed[c][0] == full[c][0]
        # K/V + Wo share of qkv stays dense: halving q rows saves < half
        assert packed["qkv"][1] > packed["qkv"][0] / 2
        # attention scales with the packed q rows exactly
        assert packed["attn"][1] == full["attn"][0] / 2

    def test_scheduler_lifetime_accounting(self):
        from repro.serving import PagePool, Scheduler, SchedulerConfig

        sched = Scheduler(SchedulerConfig(), PagePool(8, 4), 32)
        assert sched.flops_saved_pct() == {"qkv": 0.0, "attn": 0.0,
                                           "ffn": 0.0}
        sched.note_flops({"qkv": (100.0, 50.0), "attn": (10.0, 10.0),
                          "ffn": (40.0, 10.0)})
        sched.note_flops({"qkv": (100.0, 50.0), "attn": (10.0, 10.0),
                          "ffn": (40.0, 30.0)})
        pct = sched.flops_saved_pct()
        assert pct["qkv"] == 50.0 and pct["attn"] == 0.0
        assert pct["ffn"] == 50.0


# ---------------------------------------------------------------------------
# engine-level parity + config plumbing
# ---------------------------------------------------------------------------

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


class TestPackedServingEngine:
    def _run(self, cfg, compute_backend, lens=(20, 28, 12), chunk=8,
             **scfg_kw):
        scfg = ServeConfig(n_slots=3, max_len=64, page_size=4,
                           prefill_chunk=chunk,
                           attn_backend="xla_paged_decode",
                           compute_backend=compute_backend, **scfg_kw)
        eng = PagedServingEngine(cfg, _params(cfg), scfg)
        return _drain(eng, _reqs(cfg, lens)), eng

    @pytest.mark.parametrize("backend", ["packed_xla", "packed_pallas"])
    def test_bitwise_parity_at_full_capacity(self, backend):
        """Acceptance: packed serving prefill at capacity == L (the chunk
        size bucket) produces greedy outputs bit-for-bit equal to
        simulation-mode (dense-compute) SPLS."""
        cfg = _spls_cfg()
        dense, _ = self._run(cfg, "dense")
        packed, eng = self._run(cfg, backend, capacity_buckets=(8,))
        assert packed == dense
        assert eng.stats["compute_backend"] == backend

    def test_adaptive_buckets_complete_and_save_flops(self):
        """Reduced capacities: everything drains, FFN savings accrue, and
        the controller's stats reflect the bucket choices."""
        cfg = _spls_cfg(spls_kw=dict(s_threshold=0.95))
        outs, eng = self._run(cfg, "packed_xla", lens=(48, 48, 32),
                              chunk=16, capacity_margin=1.0)
        assert all(len(o) == 4 for o in outs)
        saved = eng.stats["flops_saved_pct"]
        assert saved["ffn"] > 0.0
        assert sum(eng.stats["capacity_q"]["picks"].values()) > 0

    def test_packed_without_spls_raises(self):
        cfg = _cfg()
        with pytest.raises(ValueError, match="spls.enabled"):
            PagedServingEngine(cfg, _params(cfg), ServeConfig(
                n_slots=2, max_len=64, page_size=4,
                compute_backend="packed_xla"))

    def test_misaligned_chunk_raises_naming_both(self):
        cfg = _spls_cfg()
        with pytest.raises(ValueError) as ei:
            PagedServingEngine(cfg, _params(cfg), ServeConfig(
                n_slots=2, max_len=64, page_size=4, prefill_chunk=6))
        assert "6" in str(ei.value) and "4" in str(ei.value)

    def test_auto_align_chunk_rounds_up_with_warning(self):
        cfg = _spls_cfg()
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            eng = PagedServingEngine(cfg, _params(cfg), ServeConfig(
                n_slots=2, max_len=64, page_size=4, prefill_chunk=6,
                auto_align_chunk=True))
        assert eng.scfg.prefill_chunk == 8
        assert any("auto_align_chunk" in str(x.message) for x in w)
        # aligned chunk serves correctly
        outs = _drain(eng, _reqs(cfg, (20, 12)))
        assert all(len(o) == 4 for o in outs)

    def test_function_level_alignment_error(self):
        from repro.serving import paged_prefill_chunk_spls

        cfg = _spls_cfg()
        with pytest.raises(ValueError, match="multiple"):
            jax.eval_shape(
                lambda t: paged_prefill_chunk_spls(
                    cfg, None, None, None, None, None,
                    jnp.int32(0), t, jnp.int32(6), jnp.int32(2)),
                jax.ShapeDtypeStruct((1, 6), jnp.int32))
