"""Operations and bytes of the dense model, computed from its shapes.

Multiply-accumulates count two operations.  Everything here follows the
configuration's published widths (the ``model`` block of a configuration
file) and nothing that the program reports, so an implementation that
skips work (SPLS) is charged the same dense work as one that does not.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Shape:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def from_model(cls, m: dict) -> "Shape":
        return cls(layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                   heads=m["num_attention_heads"],
                   kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
                   d_ff=m["intermediate_size"], vocab=m["vocab_size"])


def linear_flops_per_token(s: Shape) -> float:
    """Q, K, V and output projections and the gated FFN, all layers."""
    D, H, KV, Dh, F = s.d_model, s.heads, s.kv_heads, s.head_dim, s.d_ff
    per_layer = 2.0 * (D * H * Dh + 2 * D * KV * Dh + H * Dh * D + 3 * D * F)
    return per_layer * s.layers


def attention_flops(s: Shape, kv_len: int) -> float:
    """Scores and weighted values of one query over ``kv_len`` keys, all
    layers and heads."""
    return 2.0 * 2.0 * s.heads * s.head_dim * kv_len * s.layers


def head_flops(s: Shape) -> float:
    """The LM head for one position."""
    return 2.0 * s.d_model * s.vocab


def prefill_flops(s: Shape, start: int, n: int) -> float:
    """Prompt positions ``start .. start+n-1``, each attending causally to
    every earlier position and itself; the LM head once, for the last."""
    if n <= 0:
        return 0.0
    # sum of (t + 1) for t in [start, start + n)
    keys = n * start + n * (n + 1) / 2.0
    return (n * linear_flops_per_token(s) + 2.0 * 2.0 * s.heads * s.head_dim
            * keys * s.layers + head_flops(s))


def decode_flops(s: Shape, kv_len: int) -> float:
    """One generated token whose query attends to ``kv_len`` keys."""
    return linear_flops_per_token(s) + attention_flops(s, kv_len) \
        + head_flops(s)


def paged_decode_cost(s: Shape, kv_lens, dtype_bytes: int = 2):
    """(flops, bytes) of one decode attention call over the batch, all
    layers: every K and V row attended is read once, each query and output
    row moves once."""
    keys = float(sum(kv_lens))
    flops = attention_flops(s, 1) * keys
    kv_bytes = 2.0 * s.kv_heads * s.head_dim * dtype_bytes * keys * s.layers
    qo_bytes = 2.0 * len(kv_lens) * s.heads * s.head_dim * dtype_bytes \
        * s.layers
    return flops, kv_bytes + qo_bytes


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
