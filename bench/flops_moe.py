"""Operations and bytes of OLMoE-style MoE models' chip share, from the
configuration file's ``model`` and ``deployment`` blocks.

Multiply-accumulates count two operations.  The chip's share of a layer
is its whole attention (replicated on every chip), the router over every
expert of the deployment, and the pairs routed to the experts held here;
the model's work per token counts the expected held pairs,
``topk * held / router_experts``.  The grouped expert kernel's least cost
is computed from the pairs and touched experts the program counted.
"""

from __future__ import annotations

import dataclasses

from flops import roofline_seconds  # noqa: F401  (re-exported)


@dataclasses.dataclass(frozen=True)
class MoeShape:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    router_experts: int
    topk: int
    held: int

    @classmethod
    def from_conf(cls, conf: dict) -> "MoeShape":
        m, dep = conf["model"], conf["deployment"]
        return cls(layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                   heads=m["num_attention_heads"],
                   kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
                   d_ff=m["intermediate_size"], vocab=m["vocab_size"],
                   router_experts=dep["router_experts"],
                   topk=dep["experts_per_token"],
                   held=len(dep["held_experts"]))


def pair_flops(s: MoeShape) -> float:
    """One (token, expert) pair through a SiLU-gated expert."""
    return 2.0 * 3.0 * s.d_model * s.d_ff


def expert_bytes(s: MoeShape, dtype_bytes: int = 2) -> float:
    """One expert's three matrices."""
    return 3.0 * s.d_model * s.d_ff * dtype_bytes


def gmm_cost(s: MoeShape, pairs: int, touched: int, in_bytes: int = 2,
             out_bytes: int = 4):
    """(flops, bytes) the grouped expert kernel needs at least for
    ``pairs`` routed pairs over ``touched`` held experts (each summed over
    layers): every pair's FFN, each touched expert's weights read once
    (bfloat16), each pair's input row read (bfloat16) and output row
    written (float32)."""
    flops = pairs * pair_flops(s)
    nbytes = touched * expert_bytes(s) \
        + pairs * s.d_model * (in_bytes + out_bytes)
    return flops, nbytes


def token_flops(s: MoeShape) -> float:
    """One token through every layer's projections, router and expected
    held experts; attention over the context is :func:`attention_flops`."""
    D, H, KV, Dh = s.d_model, s.heads, s.kv_heads, s.head_dim
    proj = 2.0 * (D * H * Dh + 2 * D * KV * Dh + H * Dh * D)
    router = 2.0 * D * s.router_experts
    experts = s.topk * s.held / s.router_experts * pair_flops(s)
    return (proj + router + experts) * s.layers


def attention_flops(s: MoeShape, keys: float) -> float:
    """Scores and weighted values over ``keys`` query-key pairs, all
    layers and heads."""
    return 2.0 * 2.0 * s.heads * s.head_dim * keys * s.layers


def head_flops(s: MoeShape) -> float:
    """The LM head for one position."""
    return 2.0 * s.d_model * s.vocab


def prefill_flops(s: MoeShape, start: int, n: int) -> float:
    """Prompt positions ``start .. start+n-1``, each attending causally to
    every earlier position and itself; the LM head once, for the last."""
    if n <= 0:
        return 0.0
    keys = n * start + n * (n + 1) / 2.0
    return n * token_flops(s) + attention_flops(s, keys) + head_flops(s)


def decode_flops(s: MoeShape, kv_len: int) -> float:
    """One generated token whose query attends to ``kv_len`` keys."""
    return token_flops(s) + attention_flops(s, kv_len) + head_flops(s)
