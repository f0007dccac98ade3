"""Plain reference of OLMoE-1B-7B's chip share, written from the published
description (allenai/OLMoE-1B-7B-0924 ``config.json`` and the OLMoE model
code, arXiv:2409.02060): pre-norm RMSNorm blocks; multi-head attention
whose q and k projections are each RMS-normalised over their whole width
before the heads are split, then rotate-half RoPE; a sparse MoE FFN whose
router takes a softmax over every expert in float32 and the top
``num_experts_per_tok`` of it, with the gates left unnormalised where
``norm_topk_prob`` is false; SiLU-gated experts; untied LM head.

The chip holds the experts ``held_experts`` of the ``router_experts`` the
router spans, and this reference computes what the program computes: the
held experts' part of every MoE layer, each token's output the sum over
its top-k experts that are held of ``gate * expert(token)``, every held
expert run on every token and weighted by its gate (zero where not
chosen), with no sorting.  What the other chips' experts would add is
left out, here as in the program.

It runs the whole prompt and the served tokens through one full causal
forward pass, in float32 with every matrix product at ``highest``
precision, attention one head at a time, and imports nothing of the
serving program.  The only departure from the published model is the
RMSNorm gain, taken as ``1 + stored`` (see ``weights_olmoe.py``).

``precision="fp8"`` is the control, as in ``reference.py``: every matrix
product, the router's too, takes its inputs rounded to float8 e4m3, each
scaled by its own largest magnitude, and accumulates in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference import _mm, _rope


def _rms(x, gain, eps):
    """RMSNorm over the last axis (the whole projection where it is
    unsplit)."""
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + gain)


def _moe(m, precision, h, lw):
    """The held experts' part of the MoE FFN.  h: (S, D) -> (S, D)."""
    logits = _mm("sd,de->se", h, lw["router"], precision)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, m["num_experts_per_tok"])
    if m["norm_topk_prob"]:
        gates = gates / gates.sum(-1, keepdims=True)
    held = jnp.asarray(m["held_experts"], jnp.int32)
    # (S, Eh): token s's gate for held expert j, zero where not chosen
    w = jnp.sum(jnp.where(experts[:, :, None] == held[None, None, :],
                          gates[:, :, None], 0.0), axis=1)
    gate = _mm("sd,edf->esf", h, lw["w_gate"], precision, b_axis=None)
    up = _mm("sd,edf->esf", h, lw["w_up"], precision, b_axis=None)
    out = _mm("esf,efd->esd", jax.nn.silu(gate) * up, lw["w_down"],
              precision, b_axis=None)
    return jnp.einsum("se,esd->sd", w, out, precision=jax.lax.Precision.HIGHEST)


def _layer(m, precision, x, lw):
    S, D = x.shape
    H, KV, Dh = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    G = H // KV
    eps = m["rms_norm_eps"]
    h = _rms(x, lw["ln1"], eps)
    q = _rms(_mm("sd,de->se", h, lw["wq"].reshape(D, H * Dh), precision),
             lw["q_norm"], eps).reshape(S, H, Dh)
    k = _rms(_mm("sd,de->se", h, lw["wk"].reshape(D, KV * Dh), precision),
             lw["k_norm"], eps).reshape(S, KV, Dh)
    v = _mm("sd,de->se", h, lw["wv"].reshape(D, KV * Dh), precision
            ).reshape(S, KV, Dh)
    q = _rope(q, m["rope_theta"])
    k = _rope(k, m["rope_theta"])
    # query head j reads KV head j // G
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def head(qkv):
        qh, kh, vh = qkv
        s = _mm("qd,kd->qk", qh, kh, precision, a_axis=-1, b_axis=-1) \
            * Dh ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return _mm("qk,kd->qd", p, vh, precision, a_axis=-1, b_axis=0)

    o = jax.lax.map(head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                           v.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2).reshape(S, H * Dh)
    x = x + _mm("se,ed->sd", o, lw["wo"].reshape(H * Dh, D), precision)
    x = x + _moe(m, precision, _rms(x, lw["ln2"], eps), lw)
    return x, None


@functools.partial(jax.jit, static_argnames=("model", "precision"))
def _logits_at(w, tokens, positions, *, model, precision):
    m = dict(model)
    x = w["embed"][tokens].astype(jnp.float32)
    per_layer = {k: v.astype(jnp.float32) for k, v in w.items()
                 if k not in ("embed", "final_norm", "lm_head")}
    x, _ = jax.lax.scan(functools.partial(_layer, m, precision), x,
                        per_layer)
    h = _rms(x[positions], w["final_norm"], m["rms_norm_eps"])
    return _mm("pd,dv->pv", h, w["lm_head"].astype(jnp.float32), precision,
               a_axis=-1, b_axis=None)


def logits_at(w: dict, model: dict, tokens, positions,
              precision: str = "f32"):
    """Logits (len(positions), vocab) of the next token at ``positions``
    of the causal sequence ``tokens``.  ``w`` is
    ``weights_olmoe.layers(...)``; ``model`` the configuration's sizes."""
    key = tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in model.items()
        if isinstance(v, (int, float, bool, str, list))))
    return _logits_at(w, jnp.asarray(tokens, jnp.int32),
                      jnp.asarray(positions, jnp.int32), model=key,
                      precision=precision)
