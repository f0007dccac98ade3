"""Seeded random weights of OLMoE-1B-7B's chip share, made on the device.

As ``weights.py`` does for the dense model: the benchmark makes the
weights itself, laid out as the serving program stores its parameters
(one stacked block per layer, query heads grouped by KV head) and in its
storage type; :func:`layers` gives the reference the same arrays by name.
The router spans every expert of the deployment (``router_experts``);
the expert weights only the experts held here (``num_experts``).  The
qk-norm gains span the whole q and k projections.

Values: projections are normal with standard deviation ``1/sqrt(fan_in)``;
RMSNorm gains are ``1 + 0.1 * normal``, stored as the offset from 1 (the
program's convention: it multiplies by ``1 + stored``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from weights import seed_key


def shape_of(model: dict) -> dict:
    return {"L": model["num_hidden_layers"], "D": model["hidden_size"],
            "H": model["num_attention_heads"],
            "KV": model["num_key_value_heads"], "Dh": model["head_dim"],
            "F": model["intermediate_size"], "V": model["vocab_size"],
            "E": model["router_experts"], "Eh": model["num_experts"]}


def _make(shape, key, dtype):
    L, D, H, KV, Dh, F, V, E, Eh = (shape[k] for k in (
        "L", "D", "H", "KV", "Dh", "F", "V", "E", "Eh"))
    G = H // KV
    ks = iter(jax.random.split(key, 16))

    def proj(shp, fan_in, dt=dtype):
        return (jax.random.normal(next(ks), shp, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    def gain(shp):
        return (0.1 * jax.random.normal(next(ks), shp, jnp.float32)
                ).astype(dtype)

    block = {
        "ln1": gain((L, D)),
        "attn": {"wq": proj((L, D, KV, G, Dh), D),
                 "wk": proj((L, D, KV, Dh), D),
                 "wv": proj((L, D, KV, Dh), D),
                 "wo": proj((L, KV, G, Dh, D), H * Dh),
                 "q_norm": gain((L, H * Dh)),
                 "k_norm": gain((L, KV * Dh))},
        "ln2": gain((L, D)),
        "ffn": {"router": proj((L, D, E), D, jnp.float32),
                "w_gate": proj((L, Eh, D, F), D),
                "w_up": proj((L, Eh, D, F), D),
                "w_down": proj((L, Eh, F, D), F)},
    }
    return {"embed": proj((V, D), D), "periods": (block,),
            "final_norm": gain((D,)), "lm_head": proj((D, V), D)}


def make_weights(model: dict, seed: int, dtype=jnp.float32):
    """All weights in one jitted call on the default device."""
    return jax.jit(functools.partial(_make, shape_of(model), dtype=dtype))(
        seed_key(seed))


def layers(w) -> dict:
    """The reference's view: name -> array, per-layer arrays stacked on
    axis 0 (no copies)."""
    b = w["periods"][0]
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "lm_head": w["lm_head"],
            "ln1": b["ln1"], "ln2": b["ln2"],
            "wq": b["attn"]["wq"], "wk": b["attn"]["wk"],
            "wv": b["attn"]["wv"], "wo": b["attn"]["wo"],
            "q_norm": b["attn"]["q_norm"], "k_norm": b["attn"]["k_norm"],
            "router": b["ffn"]["router"], "w_gate": b["ffn"]["w_gate"],
            "w_up": b["ffn"]["w_up"], "w_down": b["ffn"]["w_down"]}
