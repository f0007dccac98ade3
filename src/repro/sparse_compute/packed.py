"""Packed execution of the SPLS-sparsified linear ops.

Two operations, both dispatched through the compute-backend registry
(:mod:`repro.sparse_compute.backend`) and both row-for-row equal to their
dense counterparts up to float32 summation order (a row subset of a dot
need not sum in the full dot's order -- see
``kernels/gathered_matmul.py``):

* :func:`packed_project_q` -- Q projection of a packed row subset in the
  structured GQA layout, RoPE'd at the rows' *original* positions.  The
  serving prefill packs Q to the **cross-head union** of critical rows:
  every head's leaders are in the union, so per-head leader recovery
  reads slots that were actually computed, and the single
  ``(C, D) @ (D, H*Dh)`` matmul keeps the MXU dense (per-head row sets
  would fragment it).
* :func:`packed_mlp` -- the dense (gated) MLP on FFN-critical token rows
  with leader broadcast, mirroring :func:`repro.models.moe.mlp_forward`
  einsum-for-einsum.  MoE blocks are not packed (their capacity routing
  already is the pack).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.sparse_exec import Compaction
from repro.models.attention import norm_k, norm_q
from repro.models.common import Activations, apply_rope, rope_freqs

from .backend import get_compute_backend

__all__ = ["packed_project_q", "packed_project_kv", "packed_mlp"]


def packed_project_q(cfg, p: dict, xn: jax.Array, positions: jax.Array,
                     perm: jax.Array, backend: str) -> jax.Array:
    """Project Q for a packed row subset (B = 1, structured layout).

    xn: (1, L, D) normalized block input; positions: (L,) original row
    ids; perm: (C,) packed source rows.  Returns ``(1, KV, G, C, Dh)``
    whose slot ``c`` is row ``perm[c]`` of
    :func:`repro.models.attention.project_qkv`'s q output (einsum row
    subset + row-wise qk-norm/RoPE), to float32 summation order -- the
    parity tests pin this.
    """
    D, KV, Dh = cfg.d_model, cfg.n_kv_heads, cfg.resolved_head_dim
    G = cfg.n_heads // KV
    C = perm.shape[0]
    wq2 = p["wq"].reshape(D, KV * G * Dh)
    be = get_compute_backend(backend)
    qg = be.gathered_matmul(xn[0], wq2, perm)            # (C, KV*G*Dh)
    q = qg.reshape(1, C, KV, G, Dh).transpose(0, 2, 3, 1, 4)
    q = q.astype(xn.dtype)
    if cfg.qk_norm:
        q = norm_q(cfg, p, q)
    pos_p = jnp.take(positions, perm)[None, :]           # (1, C)
    sin, cos = rope_freqs(pos_p, Dh, cfg.rope_theta)
    return apply_rope(q, sin[:, None, None], cos[:, None, None])


def packed_project_kv(cfg, p: dict, xn: jax.Array, positions: jax.Array,
                      perm: jax.Array, backend: str):
    """Project K/V for a packed column subset (B = 1, structured layout).

    xn: (1, L, D) normalized block input; positions: (L,) original slot
    ids; perm: (C,) packed source rows (the horizon-finalized keep
    decision of :func:`repro.core.planner.own_column_keep`, packed by
    :func:`repro.core.sparse_exec.pack_by_mask`).  Returns
    ``(k, v)`` of shape ``(1, KV, C, Dh)`` whose slot ``c`` is row
    ``perm[c]`` of :func:`repro.models.attention.project_kv`'s dense
    output (einsum row subset + row-wise k-norm/RoPE at the original
    positions), to float32 summation order -- the parity tests pin
    this.  This is the K/V half of the paper's
    end-to-end sparsity: columns the horizon vote finalized as pruned are
    never projected at all.
    """
    D, KV, Dh = cfg.d_model, cfg.n_kv_heads, cfg.resolved_head_dim
    C = perm.shape[0]
    be = get_compute_backend(backend)
    kg = be.gathered_matmul(xn[0], p["wk"].reshape(D, KV * Dh), perm)
    vg = be.gathered_matmul(xn[0], p["wv"].reshape(D, KV * Dh), perm)
    k = kg.reshape(1, C, KV, Dh).transpose(0, 2, 1, 3).astype(xn.dtype)
    v = vg.reshape(1, C, KV, Dh).transpose(0, 2, 1, 3).astype(xn.dtype)
    if cfg.qk_norm:
        k = norm_k(cfg, p, k)
    pos_p = jnp.take(positions, perm)[None, :]           # (1, C)
    sin, cos = rope_freqs(pos_p, Dh, cfg.rope_theta)
    k = apply_rope(k, sin[:, None], cos[:, None])
    return k, v


def packed_mlp(cfg, p: dict, x: jax.Array, comp: Compaction,
               backend: str) -> jax.Array:
    """Dense (gated) MLP on packed critical rows + leader broadcast.

    x: (B, L, D); comp: compaction over (B, L) (FFN-critical rows packed,
    per-row read slots resolved).  Returns (B, L, D): critical rows carry
    their own MLP output, similar rows their MFI leader's, overflow rows
    their window leader's.  Batch rows flatten into the gather indices so
    one kernel call serves the whole batch.
    """
    B, L, D = x.shape
    C = comp.perm.shape[-1]
    act = Activations.fn(cfg.ffn_activation)
    be = get_compute_backend(backend)
    perm = (comp.perm + jnp.arange(B, dtype=jnp.int32)[:, None] * L
            ).reshape(-1)
    slot = (comp.src_slot + jnp.arange(B, dtype=jnp.int32)[:, None] * C
            ).reshape(-1)
    x2 = x.reshape(B * L, D)
    up = be.gathered_matmul(x2, p["w_up"], perm)         # (B*C, F)
    if "w_gate" in p:
        up = up * act(be.gathered_matmul(x2, p["w_gate"], perm))
    else:
        up = act(up)
    up = up.astype(x.dtype)
    down = jnp.einsum("cf,fd->cd", up, p["w_down"])      # rows already packed
    return be.gather_rows(down, slot).reshape(B, L, D)
