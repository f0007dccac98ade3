"""Shared model building blocks: norms, RoPE, initializers, dtype helpers."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["dtype_of", "cast_compute", "off_compute_width", "rms_norm",
           "layer_norm", "rope_freqs", "apply_rope", "dense_init", "softcap",
           "Activations"]


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


def _keeps_dtype(path, a) -> bool:
    """Whether :func:`cast_compute` leaves leaf ``a`` at ``path`` as it
    is: not floating, or an MoE router's weights (key ``router``)."""
    return not jnp.issubdtype(a.dtype, jnp.floating) or any(
        getattr(k, "key", None) == "router" for k in path)


def cast_compute(params, dtype):
    """Floating parameters cast to the compute dtype, except an MoE
    router's weights (key ``router``), which route in float32."""
    def cast(path, a):
        return a if _keeps_dtype(path, a) else a.astype(dtype)

    return jax.tree_util.tree_map_with_path(cast, params)


def off_compute_width(params, dtype) -> list:
    """The paths of the leaves :func:`cast_compute` would change: the
    floating parameters not at ``dtype``, an MoE router's excepted.
    Reads only dtypes, so it works on arrays, tracers and
    ``ShapeDtypeStruct`` leaves alike."""
    dtype = jnp.dtype(dtype)
    return [jax.tree_util.keystr(path) for path, a
            in jax.tree_util.tree_flatten_with_path(params)[0]
            if not _keeps_dtype(path, a) and a.dtype != dtype]


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    """Gemma-2 style logit soft-capping: cap * tanh(x / cap)."""
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


def rope_freqs(positions: jax.Array, head_dim: int,
               theta: float = 10000.0) -> Tuple[jax.Array, jax.Array]:
    """(sin, cos) of shape positions.shape + (head_dim // 2,)."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """Rotate pairs.  x: (..., L, Dh); sin/cos: broadcastable (..., L, Dh/2)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def dense_init(key: jax.Array, shape: Tuple[int, ...], dtype,
               fan_in: Optional[int] = None) -> jax.Array:
    """Truncated-normal with 1/sqrt(fan_in) scaling (LeCun-ish)."""
    fan_in = fan_in or shape[0]
    std = fan_in ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


class Activations:
    """Activation registry for the FFN (gated variants use 2 input mats)."""

    @staticmethod
    def gated(name: str) -> bool:
        return name in ("silu", "gelu")

    @staticmethod
    def fn(name: str):
        return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
                "gelu_mlp": jax.nn.gelu}[name]
