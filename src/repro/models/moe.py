"""FFN layers: dense (gated) MLP and two Mixture-of-Experts layers.

* :func:`moe_forward`, the capacity formulation that training and the
  dry-runs use: einsum dispatch/combine (Shazeer et al.), the expert axis
  bound to the "model" mesh axis, so with pjit the dispatch einsum lowers
  to an all-to-all-like collective schedule chosen by SPMD.  Capacity is
  static (``cfg.moe_capacity``); tokens over capacity are dropped (their
  FFN contribution is zero and the residual carries them).
* :func:`moe_held_forward`, the dropless layer of one chip's share of an
  expert-parallel layer (``cfg.moe_held`` names the experts held here):
  it routes over every expert, keeps the (token, expert) pairs whose
  expert is held, lays them out grouped by expert and runs the grouped
  ``moe_gmm`` kernel, then adds ``gate * output`` back to each token.  No
  pair is ever dropped.  What the absent experts would add is left out:
  their chips add it in the deployment.  Configurations with
  ``moe_held`` set run it in every path.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.kernels.moe_gmm import moe_gmm, tile_rows
from repro.sharding.logical import constrain
from .common import Activations, dense_init

__all__ = ["init_mlp", "mlp_forward", "init_moe", "moe_forward",
           "moe_route", "moe_held_forward", "init_ffn", "ffn_forward",
           "MOE_STATS"]

# the counters moe_held_forward reports per call, in this order
MOE_STATS = ("moe_pairs", "moe_touched", "moe_peak")


# ---------------------------------------------------------------------------
# Dense (gated) MLP
# ---------------------------------------------------------------------------

def init_mlp(cfg: ArchConfig, key: jax.Array, dtype) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], (D, F), dtype, fan_in=D),
         "w_down": dense_init(ks[1], (F, D), dtype, fan_in=F)}
    if Activations.gated(cfg.ffn_activation):
        p["w_gate"] = dense_init(ks[2], (D, F), dtype, fan_in=D)
    return p


def mlp_forward(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    act = Activations.fn(cfg.ffn_activation)
    up = jnp.einsum("...d,df->...f", x, p["w_up"])
    if "w_gate" in p:
        up = up * act(jnp.einsum("...d,df->...f", x, p["w_gate"]))
    else:
        up = act(up)
    # NOTE: leading dim keeps its batch sharding -- a None entry in a
    # sharding constraint means *replicated*, not *unconstrained*.
    up = constrain(up, ("batch",) + (None,) * (up.ndim - 2) + ("ffn",))
    out = jnp.einsum("...f,fd->...d", up, p["w_down"])
    return constrain(out, ("batch",) + (None,) * (out.ndim - 2) + ("embed",))


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------

def init_moe(cfg: ArchConfig, key: jax.Array, dtype) -> dict:
    """The router spans every expert; the expert weights only the held
    ones (``cfg.n_held_experts``)."""
    D, F, E = cfg.d_model, cfg.d_ff, cfg.moe_experts
    Eh = cfg.n_held_experts
    ks = jax.random.split(key, 4)
    p = {"router": dense_init(ks[0], (D, E), jnp.float32, fan_in=D),
         "w_up": dense_init(ks[1], (Eh, D, F), dtype, fan_in=D),
         "w_down": dense_init(ks[2], (Eh, F, D), dtype, fan_in=F)}
    if Activations.gated(cfg.ffn_activation):
        p["w_gate"] = dense_init(ks[3], (Eh, D, F), dtype, fan_in=D)
    return p


def _dispatch_combine(probs: jax.Array, topk: int, capacity: int,
                      norm_topk: bool = True):
    """Top-k routing with per-expert capacity.

    probs: (B, L, E) router probabilities.  Returns
      dispatch: (B, L, E, C) one-hot-ish bool->dtype dispatch tensor
      combine:  (B, L, E, C) gate-weighted combine tensor
    """
    B, L, E = probs.shape
    gate_vals, experts = jax.lax.top_k(probs, topk)          # (B, L, K)
    if norm_topk:
        gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    onehot = jax.nn.one_hot(experts, E, dtype=jnp.int32)     # (B, L, K, E)
    # slot-major priority: slot k of token l gets position after all slots
    # k' < k of every token and all tokens l' < l at the same slot.
    slot_major = onehot.transpose(0, 2, 1, 3).reshape(B, topk * L, E)
    pos = jnp.cumsum(slot_major, axis=1) - slot_major        # positions before
    pos = pos.reshape(B, topk, L, E).transpose(0, 2, 1, 3)   # (B, L, K, E)
    within = (pos < capacity) & (onehot == 1)
    pos_in_e = (pos * onehot).sum(-1)                        # (B, L, K)

    cap_oh = jax.nn.one_hot(pos_in_e, capacity, dtype=probs.dtype)  # (B,L,K,C)
    keep = within.astype(probs.dtype)                        # (B, L, K, E)
    dispatch = jnp.einsum("blke,blkc->blec", keep, cap_oh)
    combine = jnp.einsum("blke,blk,blkc->blec", keep, gate_vals, cap_oh)
    return dispatch, combine


def moe_forward(cfg: ArchConfig, p: dict, x: jax.Array,
                capacity: Optional[int] = None) -> jax.Array:
    """x: (B, L, D) -> (B, L, D) through top-k experts."""
    B, L, D = x.shape
    E = cfg.moe_experts
    C = capacity or cfg.moe_capacity(L)
    act = Activations.fn(cfg.ffn_activation)

    logits = jnp.einsum("bld,de->ble", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    dispatch, combine = _dispatch_combine(probs, cfg.moe_topk, C,
                                          cfg.moe_norm_topk)
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(x.dtype)

    xin = jnp.einsum("blec,bld->becd", dispatch, x)
    xin = constrain(xin, ("batch", "experts", None, None))
    up = jnp.einsum("becd,edf->becf", xin, p["w_up"])
    if "w_gate" in p:
        up = up * act(jnp.einsum("becd,edf->becf", xin, p["w_gate"]))
    else:
        up = act(up)
    yout = jnp.einsum("becf,efd->becd", up, p["w_down"])
    yout = constrain(yout, ("batch", "experts", None, None))
    out = jnp.einsum("blec,becd->bld", combine, yout)
    return constrain(out, ("batch", "seq", "embed"))


def moe_route(cfg: ArchConfig, router: jax.Array, x: jax.Array):
    """Router logits and softmax over every expert in float32 (at full
    precision: a TPU would otherwise round a float32 product to bfloat16),
    then the top ``cfg.moe_topk``; their gates are renormalised only where
    ``cfg.moe_norm_topk``.  x: (T, D) -> gates, experts: (T, K)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg.moe_topk)
    if cfg.moe_norm_topk:
        gates = gates / gates.sum(-1, keepdims=True)
    return gates, experts


def moe_held_forward(cfg: ArchConfig, p: dict, x: jax.Array,
                     row_valid: Optional[jax.Array] = None):
    """Dropless MoE over the experts held here.  x: (..., D); row_valid:
    x's leading shape, False for rows that route to nothing (padding).

    Returns ``(y, stats)``: y like x, the held experts' part of the layer's
    output; stats (3,) int32, :data:`MOE_STATS`: the pairs routed to held
    experts, the held experts that got at least one, and the most any one
    got.  The pairs are laid out by a counting sort -- a one-hot cumsum
    gives each pair its rank in its expert's group -- so that each tile of
    ``bm`` rows holds one expert's pairs, and the grid is sized for every
    pair being held: nothing is dropped whatever the skew."""
    shape = x.shape
    D = shape[-1]
    x = x.reshape(-1, D)
    T = x.shape[0]
    K = cfg.moe_topk
    held = cfg.moe_held_ids
    Eh = len(held)
    bm = tile_rows(T, K, cfg.moe_experts)
    # one group can gain a partial tile each; at most min(K, Eh) pairs of
    # a token are held
    NT = -(-T * min(K, Eh) // bm) + Eh
    R = NT * bm
    with jax.named_scope("moe"):
        gates, experts = moe_route(cfg, p["router"], x)
        # expert id -> local group, Eh where the expert is held elsewhere
        local = np.full((cfg.moe_experts,), Eh, np.int32)
        local[list(held)] = np.arange(Eh)
        local = jnp.asarray(local)
        grp = local[experts]                                   # (T, K)
        if row_valid is not None:
            grp = jnp.where(row_valid.reshape(T, 1), grp, Eh)
        grp = grp.reshape(T * K)
        onehot = jax.nn.one_hot(grp, Eh + 1, dtype=jnp.int32)
        rank = ((jnp.cumsum(onehot, axis=0) - onehot) * onehot).sum(-1)
        counts = onehot[:, :Eh].sum(0)                         # (Eh,)
        tiles = -(-counts // bm)
        tile_end = jnp.cumsum(tiles)
        n_tiles = tile_end[-1]
        tile_start = tile_end - tiles
        dest = jnp.where(grp < Eh,
                         tile_start[jnp.minimum(grp, Eh - 1)] * bm + rank, R)
        # each row's pair (T * K on padding rows); padding rows name token
        # T: a zero input row, dropped on return
        row_pair = jnp.full((R,), T * K, jnp.int32).at[dest].set(
            jnp.arange(T * K, dtype=jnp.int32), mode="drop")
        row_tok = row_pair // K
        row_gate = gates.reshape(T * K).at[row_pair].get(mode="fill",
                                                         fill_value=0)
        last = jnp.searchsorted(tile_end, jnp.maximum(n_tiles - 1, 0),
                                side="right")
        tile_group = jnp.searchsorted(tile_end, jnp.arange(NT),
                                      side="right")
        tile_group = jnp.where(jnp.arange(NT) < n_tiles, tile_group,
                               last).astype(jnp.int32)
        tile_group = jnp.minimum(tile_group, Eh - 1)
        rows = x.at[row_tok].get(mode="fill", fill_value=0)
        out = moe_gmm(rows, p["w_gate"], p["w_up"], p["w_down"], tile_group,
                      n_tiles.reshape(1), bm=bm, act=cfg.ffn_activation)
        y = jnp.zeros((T, D), jnp.float32).at[row_tok].add(
            out * row_gate[:, None], mode="drop")
        stats = jnp.stack([counts.sum(), (counts > 0).sum(),
                           counts.max()]).astype(jnp.int32)
    return y.astype(x.dtype).reshape(shape), stats


def moe_aux_loss(probs: jax.Array, dispatch: jax.Array) -> jax.Array:
    """Load-balance auxiliary loss (Switch-style)."""
    # fraction of tokens dispatched to each expert vs mean router prob
    fe = dispatch.sum(-1).mean(axis=(0, 1))        # (E,)
    pe = probs.mean(axis=(0, 1))                   # (E,)
    return probs.shape[-1] * jnp.sum(fe * pe)


# ---------------------------------------------------------------------------
# Unified FFN entry
# ---------------------------------------------------------------------------

def init_ffn(cfg: ArchConfig, use_moe: bool, key: jax.Array, dtype) -> dict:
    return init_moe(cfg, key, dtype) if use_moe else init_mlp(cfg, key, dtype)


def ffn_forward(cfg: ArchConfig, use_moe: bool, p: dict,
                x: jax.Array) -> jax.Array:
    if not use_moe:
        return mlp_forward(cfg, p, x)
    if cfg.moe_held is not None:
        return moe_held_forward(cfg, p, x)[0]
    return moe_forward(cfg, p, x)
