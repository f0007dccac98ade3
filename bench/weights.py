"""Seeded random weights of a Qwen3-style dense model, made on the device.

The benchmark makes the weights itself, so that the plain reference takes
nothing that the program made.  They are laid out as the serving program
stores its parameters (one stacked block per layer, query heads grouped
by KV head) and in its storage type, so the program serves them as they
are; :func:`layers` gives the reference the same arrays by name.

Values: projections are normal with standard deviation ``1/sqrt(fan_in)``;
RMSNorm gains are ``1 + 0.1 * normal``, stored as the offset from 1 (the
program's convention: it multiplies by ``1 + stored``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (also above 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def _make(shape, key, dtype):
    L, D, H, KV, Dh, F, V = (shape[k] for k in
                             ("L", "D", "H", "KV", "Dh", "F", "V"))
    G = H // KV
    ks = iter(jax.random.split(key, 16))

    def proj(shp, fan_in):
        return (jax.random.normal(next(ks), shp, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    def gain(shp):
        return (0.1 * jax.random.normal(next(ks), shp, jnp.float32)
                ).astype(dtype)

    block = {
        "ln1": gain((L, D)),
        "attn": {"wq": proj((L, D, KV, G, Dh), D),
                 "wk": proj((L, D, KV, Dh), D),
                 "wv": proj((L, D, KV, Dh), D),
                 "wo": proj((L, KV, G, Dh, D), H * Dh),
                 "q_norm": gain((L, Dh)),
                 "k_norm": gain((L, Dh))},
        "ln2": gain((L, D)),
        "ffn": {"w_up": proj((L, D, F), D),
                "w_down": proj((L, F, D), F),
                "w_gate": proj((L, D, F), D)},
    }
    return {"embed": proj((V, D), D), "periods": (block,),
            "final_norm": gain((D,))}


def shape_of(model: dict) -> dict:
    return {"L": model["num_hidden_layers"], "D": model["hidden_size"],
            "H": model["num_attention_heads"],
            "KV": model["num_key_value_heads"], "Dh": model["head_dim"],
            "F": model["intermediate_size"], "V": model["vocab_size"]}


def make_weights(model: dict, seed: int, dtype=jnp.float32):
    """All weights in one jitted call on the default device."""
    shape = shape_of(model)
    return jax.jit(functools.partial(_make, shape, dtype=dtype))(
        seed_key(seed))


def layers(w) -> dict:
    """The reference's view: name -> array, per-layer arrays stacked on
    axis 0 (no copies)."""
    b = w["periods"][0]
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "ln1": b["ln1"], "ln2": b["ln2"],
            "wq": b["attn"]["wq"], "wk": b["attn"]["wk"],
            "wv": b["attn"]["wv"], "wo": b["attn"]["wo"],
            "q_norm": b["attn"]["q_norm"], "k_norm": b["attn"]["k_norm"],
            "w_up": b["ffn"]["w_up"], "w_down": b["ffn"]["w_down"],
            "w_gate": b["ffn"]["w_gate"]}
