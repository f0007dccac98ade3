"""Attention backend dispatcher: registry, auto rules, and interpret-mode
parity between every registered backend -- with and without an SPLS plan.

Parity semantics (models/README.md): without a plan all forward backends
are bit-compatible within fp32 tolerance.  With a plan, ``xla_dense`` /
``xla_packed`` realise the *simulation-mode* semantics (leader recovery +
full intra-row SPA mask) while ``pallas_flash`` / ``xla_chunked`` realise
the *hardware* semantics (leader recovery + column pruning at block
granularity; no per-element intra-row mask).  When the plan's intra-row
mask carries no information beyond causal & kv_keep, all four coincide --
the three-way equality asserted here.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, BlockCfg
from repro.core.spls import SPLSConfig, SparsityPlan, build_plan
from repro.models import (attention_forward, available_backends, forward,
                          get_backend, init_attention, init_params,
                          resolve_backend)
from repro.models.attn_backend import pallas_flash, xla_chunked, xla_dense

jax.config.update("jax_platform_name", "cpu")

ATOL = 2e-5   # fp32 online-softmax vs materialized softmax


def _cfg(**kw):
    base = dict(name="t", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                head_dim=16, d_ff=128, vocab_size=64,
                period=(BlockCfg(),), remat=False)
    base.update(kw)
    return ArchConfig(**base)


def _qkv(B=2, H=4, L=128, Dh=16, seed=0):
    """Backend-layout tensors: q (B, H, 1, L, Dh), k/v (B, H, L, Dh)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, 1, L, Dh))
    k = jax.random.normal(ks[1], (B, H, L, Dh))
    v = jax.random.normal(ks[2], (B, H, L, Dh))
    return q, k, v


def _head_plan(B=2, H=4, L=128, D=64, seed=3, **spls_kw) -> SparsityPlan:
    """A real SPLS plan reshaped to the (B, KV=H, G=1, ...) backend layout."""
    scfg = SPLSConfig(**spls_kw)
    x = jax.random.normal(jax.random.PRNGKey(seed), (B, L, D))
    wq = jax.random.normal(jax.random.PRNGKey(seed + 1), (D, D)) * 0.1
    wk = jax.random.normal(jax.random.PRNGKey(seed + 2), (D, D)) * 0.1
    plan = build_plan(x, wq, wk, H, scfg)
    return SparsityPlan(
        attn_mask=plan.attn_mask.reshape(B, H, 1, L, L),
        q_critical=plan.q_critical.reshape(B, H, 1, L),
        q_leader=plan.q_leader.reshape(B, H, 1, L),
        kv_keep=plan.kv_keep.reshape(B, H, 1, L),
        ffn_critical=plan.ffn_critical,
        ffn_leader=plan.ffn_leader,
    )


def _column_only(plan: SparsityPlan, causal: bool) -> SparsityPlan:
    """Drop the intra-row SPA mask: attn_mask := causal & kv_keep.

    This is the regime every backend (XLA and Pallas alike) can realise
    exactly, so dense == packed == chunked == pallas holds.
    """
    L = plan.kv_keep.shape[-1]
    tri = (jnp.tril(jnp.ones((L, L), bool)) if causal
           else jnp.ones((L, L), bool))
    return plan._replace(attn_mask=tri & plan.kv_keep[..., None, :])


def _block_kill(plan: SparsityPlan, lo: int, hi: int) -> SparsityPlan:
    """Kill K/V columns [lo, hi) everywhere -- whole Pallas K blocks die."""
    keep = plan.kv_keep.at[..., lo:hi].set(False)
    keep = keep.at[..., 0].set(True)  # every causal row keeps >= 1 column
    return plan._replace(kv_keep=keep,
                         attn_mask=plan.attn_mask & keep[..., None, :])


FORWARD = sorted(available_backends(decode=False))
# contiguous-cache decode backends; the paged ones (different signature)
# are covered by tests/test_paged_decode.py
DECODE = sorted(available_backends(decode=True, paged=False))


class TestRegistry:
    def test_expected_backends_registered(self):
        assert set(FORWARD) >= {"xla_dense", "xla_packed", "xla_chunked",
                                "pallas_flash"}
        assert set(DECODE) >= {"xla_dense_decode", "pallas_flash_decode"}
        assert set(available_backends(decode=True, paged=True)) >= {
            "xla_paged_decode", "pallas_paged_decode"}

    def test_unknown_name_raises(self):
        with pytest.raises(ValueError, match="unknown attention backend"):
            get_backend("nope")
        with pytest.raises(ValueError, match="unknown attention backend"):
            resolve_backend("nope", _cfg(), L=64)

    def test_kind_mismatch_falls_back_to_auto(self):
        # one cfg field drives both contexts; a choice for one side must
        # not break the other -- mismatches resolve to the auto pick
        name = resolve_backend("pallas_flash", _cfg(), L=64, decode=True)
        assert name in DECODE
        name = resolve_backend("pallas_flash_decode", _cfg(), L=64)
        assert name in FORWARD

    def test_auto_rules(self):
        cfg = _cfg()
        assert resolve_backend("auto", cfg, L=128) == "xla_dense"
        assert resolve_backend("auto", cfg, L=128,
                               platform="tpu") == "pallas_flash"
        assert resolve_backend("auto", cfg, L=16384) == "xla_chunked"
        assert resolve_backend(None, cfg, L=64, decode=True) == \
            "xla_dense_decode"
        assert resolve_backend("auto", cfg, L=64, decode=True,
                               platform="tpu") == "pallas_flash_decode"
        plan = _head_plan(L=64)
        assert resolve_backend("auto", cfg, L=64, plan=plan) == "xla_dense"
        assert resolve_backend("auto", cfg, L=64, plan=plan,
                               q_capacity=32) == "xla_packed"

    def test_auto_chunked_plan(self):
        from repro.core.spls_chunked import ChunkedPlan
        dummy = ChunkedPlan(*(jnp.zeros((1,)),) * 5)
        assert resolve_backend("auto", _cfg(), L=64,
                               plan=dummy) == "xla_chunked"
        assert resolve_backend("auto", _cfg(), L=64, plan=dummy,
                               platform="tpu") == "xla_chunked"


class TestForwardParityNoPlan:
    """Every forward backend == xla_dense on dense inputs."""

    @pytest.mark.parametrize("backend", [b for b in FORWARD
                                         if b != "xla_dense"])
    @pytest.mark.parametrize("causal,window,cap", [
        (True, None, None), (False, None, None), (True, 32, None),
        (False, 32, None), (True, None, 30.0), (True, 32, 30.0),
        (False, 32, 30.0),
    ])
    def test_matches_dense(self, backend, causal, window, cap):
        cfg = _cfg(causal=causal, attn_softcap=cap)
        q, k, v = _qkv()
        want = xla_dense(cfg, q, k, v, window=window)
        got = get_backend(backend)(cfg, q, k, v, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=ATOL, err_msg=backend)

    @pytest.mark.parametrize("backend", FORWARD)
    def test_ragged_length(self, backend):
        """L that tiles into neither Pallas blocks nor KV chunks."""
        cfg = _cfg()
        q, k, v = _qkv(L=100)
        want = xla_dense(cfg, q, k, v)
        got = get_backend(backend)(cfg, q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=ATOL, err_msg=backend)


class TestForwardParityWithPlan:
    @pytest.mark.parametrize("causal", [True, False])
    def test_three_way_parity_column_only_plan(self, causal):
        """dense == packed == chunked == pallas under column-only sparsity."""
        cfg = _cfg(causal=causal)
        q, k, v = _qkv(seed=7)
        plan = _column_only(_head_plan(causal=causal), causal)
        outs = {b: get_backend(b)(cfg, q, k, v, plan=plan) for b in
                ("xla_dense", "xla_packed", "xla_chunked", "pallas_flash")}
        for b, o in outs.items():
            np.testing.assert_allclose(
                np.asarray(o), np.asarray(outs["xla_dense"]), atol=ATOL,
                err_msg=b)

    def test_parity_with_dead_kv_blocks(self):
        """kv_keep killing entire 128-wide K blocks (the acceptance case)."""
        cfg = _cfg(causal=True)
        B, H, L = 2, 4, 256
        q, k, v = _qkv(L=L, seed=11)
        plan = _column_only(_head_plan(L=L, causal=True), True)
        plan = _block_kill(plan, 128, 256)  # second Pallas K block fully dead
        outs = {b: get_backend(b)(cfg, q, k, v, plan=plan) for b in
                ("xla_dense", "xla_packed", "xla_chunked", "pallas_flash")}
        for b, o in outs.items():
            np.testing.assert_allclose(
                np.asarray(o), np.asarray(outs["xla_dense"]), atol=ATOL,
                err_msg=b)

    def test_full_spls_plan_simulation_vs_hardware_split(self):
        """With intra-row top-k: dense == packed and pallas == chunked."""
        cfg = _cfg(causal=True)
        q, k, v = _qkv(seed=13)
        plan = _head_plan(causal=True, k_ratio=0.2, s_threshold=0.7,
                          f_threshold=2)
        dense = xla_dense(cfg, q, k, v, plan=plan)
        packed = get_backend("xla_packed")(cfg, q, k, v, plan=plan)
        np.testing.assert_allclose(np.asarray(packed), np.asarray(dense),
                                   atol=ATOL)
        chunked = xla_chunked(cfg, q, k, v, plan=plan)
        flash = pallas_flash(cfg, q, k, v, plan=plan)
        np.testing.assert_allclose(np.asarray(flash), np.asarray(chunked),
                                   atol=ATOL)

    def test_pallas_packs_critical_rows_reduced_capacity(self):
        """Real row packing: capacity < L, rounded to whole q blocks."""
        cfg = _cfg(causal=True)
        L = 256
        q, k, v = _qkv(L=L, seed=17)
        plan = _column_only(_head_plan(L=L, causal=True, s_threshold=0.95,
                                       k_ratio=0.1), True)
        ncrit = int(plan.q_critical.sum(-1).max())
        cap = -(-ncrit // 128) * 128   # both packers see the same capacity
        assert cap < L, "want an actually reduced capacity for this test"
        flash = pallas_flash(cfg, q, k, v, plan=plan, q_capacity=cap)
        chunked = xla_chunked(cfg, q, k, v, plan=plan, q_capacity=cap)
        np.testing.assert_allclose(np.asarray(flash), np.asarray(chunked),
                                   atol=ATOL)
        # critical rows also agree with unpacked simulation numerics
        dense = xla_dense(cfg, q, k, v, plan=plan)
        crit = np.asarray(plan.q_critical[..., None] &
                          jnp.ones(flash.shape, bool))
        np.testing.assert_allclose(np.asarray(flash)[crit],
                                   np.asarray(dense)[crit], atol=ATOL)

    @pytest.mark.parametrize("causal", [True, False])
    def test_window_plus_plan_all_backends(self, causal):
        """SPLS + sliding window: every backend applies the same window
        (the XLA paths through the mask, pallas/chunked through indices)."""
        cfg = _cfg(causal=causal)
        q, k, v = _qkv(seed=19)
        plan = _column_only(_head_plan(causal=causal), causal)
        outs = {b: get_backend(b)(cfg, q, k, v, window=32, plan=plan) for b
                in ("xla_dense", "xla_packed", "xla_chunked", "pallas_flash")}
        for b, o in outs.items():
            np.testing.assert_allclose(
                np.asarray(o), np.asarray(outs["xla_dense"]), atol=ATOL,
                err_msg=b)


class TestAttentionForwardDispatch:
    """cfg.attn_backend / backend= thread through the full mixer."""

    @pytest.mark.parametrize("backend", FORWARD)
    def test_model_forward_invariant_to_backend(self, backend):
        cfg = _cfg()
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0,
                                  cfg.vocab_size)
        want = forward(cfg, params, toks)
        got = forward(dataclasses.replace(cfg, attn_backend=backend),
                      params, toks)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, err_msg=backend)

    @pytest.mark.parametrize("backend", FORWARD)
    def test_attention_forward_backend_arg(self, backend):
        cfg = _cfg()
        p = init_attention(cfg, jax.random.PRNGKey(2), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, cfg.d_model))
        want = attention_forward(cfg, p, x)
        got = attention_forward(cfg, p, x, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=ATOL, err_msg=backend)

    @pytest.mark.parametrize("backend", FORWARD)
    def test_gqa_model_forward_invariant(self, backend):
        """Grouped-KV (n_kv_heads < n_heads) through every backend."""
        cfg = _cfg(n_heads=4, n_kv_heads=2)
        params = init_params(cfg, jax.random.PRNGKey(7))
        toks = jax.random.randint(jax.random.PRNGKey(8), (2, 48), 0,
                                  cfg.vocab_size)
        want = forward(cfg, params, toks)
        got = forward(dataclasses.replace(cfg, attn_backend=backend),
                      params, toks)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, err_msg=backend)

    def test_chunked_ragged_kv_capacity(self):
        """Ck not a multiple of kv_chunk: internal dead-column padding
        keeps the chunk grid (and the result) intact."""
        from repro.core.sparse_exec import spls_attention_chunked
        cfg = _cfg()
        q, k, v = _qkv(L=64, seed=23)
        plan = _column_only(_head_plan(L=64, causal=True), True)
        ragged = spls_attention_chunked(q, k, v, plan, 64, 48,
                                        kv_chunk=32, causal=True)
        single = spls_attention_chunked(q, k, v, plan, 64, 48,
                                        kv_chunk=48, causal=True)
        np.testing.assert_allclose(np.asarray(ragged), np.asarray(single),
                                   atol=ATOL)

    def test_block_forward_and_decode_backend_args(self):
        """blocks.py threads attn_backend= through to the mixer."""
        from repro.models import (block_decode, block_forward, init_block,
                                  init_block_cache)
        cfg = _cfg()
        blk = cfg.period[0]
        p = init_block(cfg, blk, jax.random.PRNGKey(4), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.d_model))
        want = block_forward(cfg, blk, p, x)
        got = block_forward(cfg, blk, p, x, attn_backend="pallas_flash")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)
        cache = init_block_cache(cfg, blk, 2, 16, jnp.float32)
        x1 = jax.random.normal(jax.random.PRNGKey(6), (2, 1, cfg.d_model))
        pos = jnp.asarray([3, 7])
        want1, _ = block_decode(cfg, blk, p, x1, cache, pos)
        got1, _ = block_decode(cfg, blk, p, x1, cache, pos,
                               attn_backend="pallas_flash_decode")
        np.testing.assert_allclose(np.asarray(got1), np.asarray(want1),
                                   atol=1e-4)

    def test_spls_model_forward_all_backends_finite(self):
        spls = SPLSConfig(enabled=True, k_ratio=0.3, s_threshold=0.6,
                          f_threshold=1, window=4)
        for backend in FORWARD:
            cfg = _cfg(spls=spls, attn_backend=backend)
            params = init_params(cfg, jax.random.PRNGKey(0))
            toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                      cfg.vocab_size)
            out = forward(cfg, params, toks)
            assert np.isfinite(np.asarray(out)).all(), backend


class TestDecodeParity:
    def _decode_inputs(self, B=2, KV=2, G=2, S=96, Dh=16, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (B, KV, G, Dh))
        k = jax.random.normal(ks[1], (B, KV, S, Dh))
        v = jax.random.normal(ks[2], (B, KV, S, Dh))
        pos = jnp.asarray([S - 1, S // 3])
        return q, k, v, pos

    @pytest.mark.parametrize("window,cap", [(None, None), (24, None),
                                            (None, 30.0)])
    def test_backends_match_oracle(self, window, cap):
        from repro.kernels.ref import flash_decode_ref
        cfg = _cfg(attn_softcap=cap)
        q, k, v, pos = self._decode_inputs()
        want = flash_decode_ref(q, k, v, pos, softcap=cap, window=window)
        for b in DECODE:
            got = get_backend(b)(cfg, q, k, v, pos=pos, window=window)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=ATOL, err_msg=b)

    def test_pallas_decode_ragged_cache(self):
        """S not a multiple of the decode block -> internal padding."""
        cfg = _cfg()
        q, k, v, pos = self._decode_inputs(S=600)
        want = get_backend("xla_dense_decode")(cfg, q, k, v, pos=pos)
        got = get_backend("pallas_flash_decode")(cfg, q, k, v, pos=pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=ATOL)
