"""Plain reference of the Qwen3 dense decoder, written from the published
description (Qwen/Qwen3-0.6B ``config.json`` and the Qwen3 model code:
pre-norm RMSNorm blocks, grouped-query attention with per-head RMSNorm on
queries and keys before rotary embeddings, rotate-half RoPE, a SiLU-gated
FFN, tied embeddings).

It runs the whole prompt and the served tokens through one full causal
forward pass, in float32 with every matrix product at ``highest``
precision, and imports nothing of the serving program.  The only
departure from the published model is the RMSNorm gain, taken as
``1 + stored`` (see ``weights.py``).

``precision="fp8"`` is the control: every matrix product takes its inputs
rounded to float8 e4m3, each scaled by its own largest magnitude (per
tensor for weights, per row for activations), and accumulates in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(eq, a, b, precision, a_axis=-1, b_axis=None):
    if precision == "fp8":
        a = _fp8(a, a_axis)
        b = _fp8(b, b_axis)
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, gain, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + gain)


def _rope(x, theta):
    """x: (S, heads, Dh); rotate-half rotary embedding at positions 0..S-1."""
    S, _, Dh = x.shape
    half = Dh // 2
    inv = 1.0 / theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(m, precision, x, lw):
    S, D = x.shape
    H, KV, Dh = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    G = H // KV
    eps = m["rms_norm_eps"]
    h = _rms(x, lw["ln1"], eps)
    q = _mm("sd,de->se", h, lw["wq"].reshape(D, H * Dh), precision,
            b_axis=None).reshape(S, H, Dh)
    k = _mm("sd,de->se", h, lw["wk"].reshape(D, KV * Dh), precision
            ).reshape(S, KV, Dh)
    v = _mm("sd,de->se", h, lw["wv"].reshape(D, KV * Dh), precision
            ).reshape(S, KV, Dh)
    q = _rope(_rms(q, lw["q_norm"], eps), m["rope_theta"])
    k = _rope(_rms(k, lw["k_norm"], eps), m["rope_theta"])
    # query head j reads KV head j // G
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    s = _mm("qhd,khd->hqk", q, k, precision, a_axis=-1, b_axis=-1) \
        * Dh ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("hqk,khd->qhd", p, v, precision, a_axis=-1, b_axis=0
            ).reshape(S, H * Dh)
    x = x + _mm("se,ed->sd", o, lw["wo"].reshape(H * Dh, D), precision)
    h2 = _rms(x, lw["ln2"], eps)
    gate = _mm("sd,df->sf", h2, lw["w_gate"], precision)
    up = _mm("sd,df->sf", h2, lw["w_up"], precision)
    x = x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, lw["w_down"],
                precision)
    return x, None


@functools.partial(jax.jit, static_argnames=("model", "precision"))
def _logits_at(w, tokens, positions, *, model, precision):
    m = dict(model)
    x = w["embed"][tokens].astype(jnp.float32)
    per_layer = {k: v.astype(jnp.float32) for k, v in w.items()
                 if k not in ("embed", "final_norm")}
    x, _ = jax.lax.scan(functools.partial(_layer, m, precision), x,
                        per_layer)
    h = _rms(x[positions], w["final_norm"], m["rms_norm_eps"])
    return _mm("pd,vd->pv", h, w["embed"].astype(jnp.float32), precision,
               a_axis=-1, b_axis=None)


def logits_at(w: dict, model: dict, tokens, positions,
              precision: str = "f32"):
    """Logits (len(positions), vocab) of the next token at ``positions``
    of the causal sequence ``tokens``.  ``w`` is ``weights.layers(...)``;
    ``model`` the configuration's published sizes."""
    key = tuple(sorted((k, v) for k, v in model.items()
                       if isinstance(v, (int, float, bool, str))))
    return _logits_at(w, jnp.asarray(tokens, jnp.int32),
                      jnp.asarray(positions, jnp.int32), model=key,
                      precision=precision)
