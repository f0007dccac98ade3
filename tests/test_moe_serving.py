"""The held-expert MoE layer and OLMoE serving on the paged path.

At a small OLMoE (d 64, 4 heads, 16 experts, 4 per token, experts 0-3
held: ``get_config("olmoe-1b-7b-ep8").smoke()``) on seeded random
weights, the paged engine's logits after chunked prefill and then decode
through the page cache are compared with the benchmark's plain float32
reference (``bench/reference_olmoe.py``).  Around that: the share test
(the four shares of a layer add up to the uncut layer), droplessness
under full skew, padded rows routing to nothing, the grouped ``moe_gmm``
kernel against a plain formula, full-width qk-norm against a hand-written
formula, and Qwen3's per-head path unchanged.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels.moe_gmm import moe_gmm
from repro.models import abstract_params, init_params
from repro.models.attention import project_qkv
from repro.models.common import cast_compute
from repro.models.moe import MOE_STATS, moe_held_forward
from repro.serving import PagedServingEngine, Request, ServeConfig

jax.config.update("jax_platform_name", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench"))
import reference_olmoe  # noqa: E402
import weights_olmoe  # noqa: E402

# float32 compute end to end: the engine and the reference differ only
# in summation order (and the engine's per-group tiles), so 1e-4 on logits
# of magnitude ~1-10 is rounding with a wide margin, while any dropped
# pair, unnormalised-gate slip or per-head qk-norm moves them by >1e-2
LOGIT_ATOL = 1e-4
# one layer's output, same argument
LAYER_ATOL = 1e-5


def _cfg(**kw):
    cfg = get_config("olmoe-1b-7b-ep8").smoke()
    return dataclasses.replace(cfg, remat=False, **kw)


def _model(cfg):
    """The configuration file's ``model`` block for a config."""
    return {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.resolved_head_dim,
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "num_experts": cfg.n_held_experts,
            "num_experts_per_tok": cfg.moe_topk,
            "norm_topk_prob": cfg.moe_norm_topk,
            "router_experts": cfg.moe_experts,
            "held_experts": list(cfg.moe_held_ids)}


_W = {}


def _weights(cfg, seed=7):
    key = (cfg.name, cfg.moe_held, seed)
    if key not in _W:
        _W[key] = weights_olmoe.make_weights(_model(cfg), seed)
    return _W[key]


def _moe_params(cfg, seed=3, scale=1.0):
    """One MoE layer's parameters for every expert (held or not)."""
    D, F, E = cfg.d_model, cfg.d_ff, cfg.moe_experts
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    n = lambda k, s, fan: jax.random.normal(k, s) * fan ** -0.5
    return {"router": n(ks[0], (D, E), D) * scale,
            "w_gate": n(ks[1], (E, D, F), D), "w_up": n(ks[2], (E, D, F), D),
            "w_down": n(ks[3], (E, F, D), F)}


def _share(p, held):
    idx = jnp.asarray(held)
    return {"router": p["router"], "w_gate": p["w_gate"][idx],
            "w_up": p["w_up"][idx], "w_down": p["w_down"][idx]}


def _ref_layer(cfg, p, x, held):
    """bench/reference_olmoe.py's MoE FFN for the experts ``held``."""
    m = _model(dataclasses.replace(cfg, moe_held=tuple(held)))
    return reference_olmoe._moe(m, "f32", x, _share(p, held))


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

def test_olmoe_config_is_the_published_one():
    c = get_config("olmoe-1b-7b")
    assert (c.norm_eps, c.moe_norm_topk, c.qk_norm_mode) == (1e-5, False,
                                                            "full")
    assert (c.n_heads, c.n_kv_heads, c.moe_experts, c.moe_topk) == (
        16, 16, 64, 8)
    ep8 = get_config("olmoe-1b-7b-ep8")
    assert ep8.moe_held == tuple(range(8)) and ep8.moe_experts == 64
    # 67.25 M a layer x 16 + embedding and head: the chip's 1.282 B
    assert abs(ep8.param_count() - 1.282e9) < 0.001e9


def test_smoke_keeps_a_held_share():
    c = _cfg()
    assert (c.moe_experts, c.moe_topk, c.moe_held) == (16, 4, (0, 1, 2, 3))
    assert (c.d_model, c.n_heads) == (64, 4)
    p = abstract_params(c)["periods"][0]
    assert p["ffn"]["router"].shape == (c.n_layers, 64, 16)
    assert p["ffn"]["w_gate"].shape == (c.n_layers, 4, 64, c.d_ff)
    assert p["attn"]["q_norm"].shape == (c.n_layers, 4 * 16)
    assert p["attn"]["k_norm"].shape == (c.n_layers, 4 * 16)


def test_benchmark_weights_match_the_program_layout():
    cfg = _cfg()
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                        abstract_params(cfg))
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), _weights(cfg))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert jax.tree.leaves(want) == jax.tree.leaves(got)


def test_router_stays_float32_in_the_compute_cast():
    cfg = _cfg()
    p = cast_compute(init_params(cfg, jax.random.PRNGKey(0))["periods"],
                     jnp.bfloat16)[0]
    assert p["ffn"]["router"].dtype == jnp.float32
    assert p["ffn"]["w_gate"].dtype == jnp.bfloat16
    assert p["attn"]["q_norm"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("norm_topk", [False, True])
def test_layer_matches_the_reference(norm_topk):
    cfg = _cfg(moe_norm_topk=norm_topk)
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, cfg.d_model))
    held = cfg.moe_held_ids
    y, stats = moe_held_forward(cfg, _share(p, held), x)
    np.testing.assert_allclose(y, _ref_layer(cfg, p, x, held),
                               atol=LAYER_ATOL)
    assert stats.shape == (len(MOE_STATS),)


def test_four_shares_add_up_to_the_uncut_layer():
    """Model-configs section 4: experts 0-3, 4-7, 8-11 and 12-15 held on
    four chips; their outputs sum to the uncut reference layer's."""
    cfg = _cfg()
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, cfg.d_model))
    shares = [tuple(range(i, i + 4)) for i in range(0, 16, 4)]
    total = sum(moe_held_forward(dataclasses.replace(cfg, moe_held=h),
                                 _share(p, h), x)[0] for h in shares)
    uncut = _ref_layer(cfg, p, x.reshape(-1, cfg.d_model), tuple(range(16)))
    np.testing.assert_allclose(total.reshape(-1, cfg.d_model), uncut,
                               atol=LAYER_ATOL)


def test_dropless_under_full_skew():
    """Every token the same id: all route to the same 4 experts, all of
    them held here, so each held expert gets every token -- many times a
    tile -- and none is dropped."""
    cfg = _cfg()
    p = _moe_params(cfg)
    T = 64
    x0 = jax.random.normal(jax.random.PRNGKey(3), (1, cfg.d_model))
    x = jnp.broadcast_to(x0, (T, cfg.d_model))
    # the held experts' router columns lean towards the token: their
    # logits gain 0.2 |x|^2 ~ 13
    p["router"] = p["router"].at[:, :4].add(0.2 * x0.T)
    y, stats = moe_held_forward(cfg, _share(p, cfg.moe_held_ids), x)
    want = _ref_layer(cfg, p, x, cfg.moe_held_ids)
    np.testing.assert_allclose(y, want, atol=LAYER_ATOL)
    assert np.abs(np.asarray(want)).min(axis=-1).max() > 0
    np.testing.assert_array_equal(stats, [4 * T, 4, T])


def test_padded_rows_route_to_nothing():
    cfg = _cfg()
    p = _share(_moe_params(cfg), cfg.moe_held_ids)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 16, cfg.d_model))
    valid = (jnp.arange(16) < 5)[None, :]
    y, stats = moe_held_forward(cfg, p, x, valid)
    y5, stats5 = moe_held_forward(cfg, p, x[:, :5])
    np.testing.assert_array_equal(np.asarray(y[0, 5:]), 0.0)
    np.testing.assert_allclose(y[:, :5], y5, atol=LAYER_ATOL)
    np.testing.assert_array_equal(stats, stats5)


def test_moe_gmm_against_a_plain_formula():
    """Tiles of 16 rows, experts 2, 0, 0, 1 and two dead tiles."""
    E, D, F, bm = 3, 32, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    wg = jax.random.normal(ks[0], (E, D, F)) * D ** -0.5
    wu = jax.random.normal(ks[1], (E, D, F)) * D ** -0.5
    wd = jax.random.normal(ks[2], (E, F, D)) * F ** -0.5
    x = jax.random.normal(ks[3], (6 * bm, D))
    groups = [2, 0, 0, 1, 1, 1]      # the dead tiles repeat the last live
    out = moe_gmm(x, wg, wu, wd, jnp.asarray(groups, jnp.int32),
                  jnp.asarray([4], jnp.int32), bm=bm, bf=32)
    for t, g in enumerate(groups[:4]):
        xt = x[t * bm:(t + 1) * bm]
        want = (jax.nn.silu(xt @ wg[g]) * (xt @ wu[g])) @ wd[g]
        np.testing.assert_allclose(out[t * bm:(t + 1) * bm], want,
                                   atol=1e-4)


# ---------------------------------------------------------------------------
# attention: full-width qk-norm
# ---------------------------------------------------------------------------

def _np_rms(x, g, eps):
    x = np.asarray(x, np.float64)
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1 + g)


@pytest.mark.parametrize("mode", ["full", "head"])
def test_qk_norm_against_a_hand_written_formula(mode):
    """At position 0 RoPE is the identity, so q and k are the projections
    normalised -- over the whole projection before the head split
    (``full``, OLMoE) or over each head (``head``, Qwen3)."""
    arch = "olmoe-1b-7b-ep8" if mode == "full" else "qwen3-0.6b"
    cfg = get_config(arch).smoke()
    assert cfg.qk_norm_mode == mode
    p = init_params(cfg, jax.random.PRNGKey(0))["periods"][0]["attn"]
    p = jax.tree.map(lambda a: a[0], p)
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    p["q_norm"] = 0.1 * jax.random.normal(ks[0], p["q_norm"].shape)
    p["k_norm"] = 0.1 * jax.random.normal(ks[1], p["k_norm"].shape)
    D, KV, Dh = cfg.d_model, cfg.n_kv_heads, cfg.resolved_head_dim
    H = cfg.n_heads
    x = jax.random.normal(ks[2], (1, 5, D))
    q, k, _ = project_qkv(cfg, p, x, jnp.zeros((1, 5), jnp.int32))
    qf = np.asarray(x[0]) @ np.asarray(p["wq"]).reshape(D, H * Dh)
    kf = np.asarray(x[0]) @ np.asarray(p["wk"]).reshape(D, KV * Dh)
    gq, gk = np.asarray(p["q_norm"]), np.asarray(p["k_norm"])
    eps = cfg.norm_eps
    if mode == "full":
        assert gq.shape == (H * Dh,) and gk.shape == (KV * Dh,)
        qw = _np_rms(qf, gq, eps).reshape(5, KV, H // KV, Dh)
        kw = _np_rms(kf, gk, eps).reshape(5, KV, Dh)
    else:
        assert gq.shape == (Dh,) and gk.shape == (Dh,)
        qw = _np_rms(qf.reshape(5, KV, H // KV, Dh), gq, eps)
        kw = _np_rms(kf.reshape(5, KV, Dh), gk, eps)
    np.testing.assert_allclose(q[0], qw.transpose(1, 2, 0, 3), atol=1e-5)
    np.testing.assert_allclose(k[0], kw.transpose(1, 0, 2), atol=1e-5)


def test_qwen3_per_head_program_is_unchanged():
    """Qwen3 keeps (Dh,) gains, and its chunk step has no MoE op."""
    cfg = dataclasses.replace(get_config("qwen3-0.6b").smoke(), remat=False)
    p = abstract_params(cfg)["periods"][0]["attn"]
    assert p["q_norm"].shape == (cfg.n_layers, cfg.resolved_head_dim)
    eng = PagedServingEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                             ServeConfig(n_slots=2, max_len=32, page_size=4,
                                         prefill_chunk=8))
    hlo = eng._chunk.lower(eng.params, eng.cache, eng.pos_pages,
                           jnp.zeros((8,), jnp.int32),
                           jnp.asarray(0, jnp.int32),
                           jnp.zeros((1, 8), jnp.int32),
                           jnp.asarray(8, jnp.int32)).as_text()
    assert "moe" not in hlo


# ---------------------------------------------------------------------------
# the paged engine against the reference
# ---------------------------------------------------------------------------

def _served(cfg, lens, max_new=5):
    """Serve each prompt alone (one active slot), keeping the logits of
    its first token and of each decode step."""
    w = _weights(cfg)
    eng = PagedServingEngine(cfg, w, ServeConfig(
        n_slots=2, max_len=64, page_size=4, prefill_chunk=8))
    step, rows = eng._decode, []

    def recording(*a):
        out = step(*a)
        active = np.nonzero(np.asarray(a[4]) > 0)[0]   # kv_len > 0
        assert len(active) == 1
        rows.append(np.asarray(out[0][active[0], 0]))
        return out

    eng._decode = recording
    rng = np.random.default_rng(0)
    served = []
    for i, n in enumerate(lens):
        r = Request(rid=i, prompt=jnp.asarray(rng.integers(
            0, cfg.vocab_size, size=n), jnp.int32), max_new_tokens=max_new,
            return_logits=True)
        rows.clear()
        eng.submit(r)
        eng.run_until_drained()
        served.append((r, [r.first_logits] + list(rows)))
    return eng, w, served


def test_engine_logits_match_the_reference_through_chunks_and_decode():
    """Prompts of 21 and 13 tokens prefill in 8-token chunks (the last
    padded), one of 6 whole; then 4 decode steps each through the page
    cache.  Every first-token row and every decode row is compared with
    the reference's full forward pass over prompt and served tokens."""
    cfg = _cfg()
    _, w, served = _served(cfg, [21, 13, 6])
    view, model = weights_olmoe.layers(w), _model(cfg)
    for r, logits in served:
        lp = len(r.prompt)
        assert len(logits) == len(r.output) == 5
        seq = np.concatenate([np.asarray(r.prompt),
                              np.asarray(r.output[:-1])])
        ref = np.asarray(reference_olmoe.logits_at(
            view, model, seq, lp - 1 + np.arange(len(r.output))))
        np.testing.assert_allclose(np.stack(logits), ref, atol=LOGIT_ATOL)
        assert r.output == ref.argmax(-1).tolist()


def test_engine_spans_carry_the_expert_counters():
    cfg = _cfg()
    eng, _, _ = _served(cfg, [21, 6])
    ev = [e for e in eng.telemetry.trace.events if e["ph"] == "B"
          and e["name"] in ("prefill_chunk", "decode_tick")]
    assert {e["name"] for e in ev} == {"prefill_chunk", "decode_tick"}
    Eh, K, L = cfg.n_held_experts, cfg.moe_topk, cfg.n_layers
    for e in ev:
        a = e["args"]
        rows = a["valid"] if e["name"] == "prefill_chunk" else a["n_active"]
        assert 0 <= a["moe_pairs"] <= rows * min(K, Eh) * L
        assert a["moe_touched"] <= Eh * L
        assert a["moe_peak"] <= rows
        assert (a["moe_pairs"] > 0) == (a["moe_touched"] > 0)


def test_dense_engine_spans_carry_no_expert_counters():
    cfg = dataclasses.replace(get_config("qwen3-0.6b").smoke(), remat=False)
    eng = PagedServingEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                             ServeConfig(n_slots=2, max_len=32, page_size=4,
                                         prefill_chunk=8))
    eng.submit(Request(rid=0, prompt=jnp.arange(11, dtype=jnp.int32),
                       max_new_tokens=3))
    eng.run_until_drained()
    for e in eng.telemetry.trace.events:
        assert not set(MOE_STATS) & set(e.get("args", {}))
