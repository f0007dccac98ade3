"""Pallas TPU kernel: blockwise flash attention with SPLS support.

Online-softmax attention tiled for VMEM, with the features the assigned
archs + the paper's technique need:

  * causal and sliding-window (gemma2 / h2o-danube / jamba) masking with
    *block-level skipping* -- fully-masked (q-block, k-block) pairs are never
    computed, so SWA cost is O(L * window), not O(L^2);
  * gemma2-style logit soft-capping;
  * an optional per-position ``kv_keep`` mask -- the SPLS column-pruning
    mask (zero SPA columns).  Dead KV blocks (all-False) are skipped whole,
    which is exactly how the accelerator's column sparsity maps onto a tiled
    TPU kernel: structured block skips instead of per-element clock gating;
  * an optional per-row ``q_pos`` index map -- the original sequence
    position of each (possibly packed) query row.  This is what lets the
    SPLS row sparsity (critical rows packed to capacity, similar rows
    recovered from their leader) run through the kernel: causal and window
    masks are evaluated against the original positions, and the causal /
    window block-skip predicates use the min/max position in the q tile;
  * ragged lengths: ``Lq % block_q != 0`` / ``Lk % block_k != 0`` are
    handled by zero-padding; padded K columns are killed through the keep
    mask and padded Q rows are sliced off the output.

Grid: (B*H, Lq/bq, Lk/bk), K innermost.  Running max / denominator / output
accumulator live in VMEM scratch and are rescaled per K step; the output is
written once on the final K step.

Block-skip boundary conventions (audited against ``ref.flash_attention_ref``
by ``tests/test_kernels.py::TestFlashAttentionBoundaries``):

  * causal keeps (i, j) iff ``j <= i``; a K block starting at ``k_start`` is
    live iff ``k_start <= max(q_pos in block)`` (block-index path:
    ``q_start + bq - 1``);
  * window keeps (i, j) iff ``i - j < window``; with ``causal=False`` the
    window is symmetric (``|i - j| < window``), matching the XLA band mask.
    A K block is live iff its last column
    ``k_start + bk - 1 > min(q_pos) - window`` (and, non-causal, its first
    column ``k_start < max(q_pos) + window``);
  * a keep-masked K block is live iff any keep bit in it is set.

Each predicate is exact for its own mask, and the conjunction is safe
because the per-row live column sets are contiguous and overlap across
consecutive rows, so a block passing every block-level test always contains
at least one live (i, j) pair.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve_interpret

__all__ = ["flash_attention"]

_NEG = -1e30


def _make_kernel(*, scale, causal, window, softcap, bq, bk, nk,
                 has_qpos, has_keep):
    """Build a kernel body for the given optional-input combination.

    Ref order: q, k, v, [q_pos], [kv_keep], o, then scratch (m, l, acc).
    """

    def kernel(*refs):
        q_ref, k_ref, v_ref = refs[:3]
        idx = 3
        qpos_ref = None
        if has_qpos:
            qpos_ref = refs[idx]
            idx += 1
        keep_ref = None
        if has_keep:
            keep_ref = refs[idx]
            idx += 1
        o_ref, m_scr, l_scr, acc_scr = refs[idx:idx + 4]

        ik = pl.program_id(2)
        iq = pl.program_id(1)

        @pl.when(ik == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, _NEG)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        q_start = iq * bq
        k_start = ik * bk
        if has_qpos:
            qpos = qpos_ref[0]                       # (bq, 1) original row ids
            q_lo, q_hi = jnp.min(qpos), jnp.max(qpos)
        else:
            q_lo, q_hi = q_start, q_start + bq - 1
        # block-level skip: causal (k block entirely in the future) and
        # window (k block entirely behind the window of every q row here)
        live = True
        if causal:
            live = jnp.logical_and(live, k_start <= q_hi)
        if window is not None:
            live = jnp.logical_and(live, k_start + bk - 1 > q_lo - window)
            if not causal:  # symmetric window: future side masks too
                live = jnp.logical_and(live, k_start < q_hi + window)
        if keep_ref is not None:
            live = jnp.logical_and(live, jnp.max(keep_ref[0]) > 0)

        @pl.when(live)
        def _compute():
            q = q_ref[0].astype(jnp.float32)
            k = k_ref[0].astype(jnp.float32)
            v = v_ref[0].astype(jnp.float32)
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            if softcap is not None:
                s = jnp.tanh(s / softcap) * softcap
            if has_qpos:
                qi = jnp.broadcast_to(qpos, (bq, bk))
            else:
                qi = q_start + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 0)
            kj = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask &= kj <= qi
            if window is not None:
                mask &= qi - kj < window
                if not causal:
                    mask &= kj - qi < window
            if keep_ref is not None:
                mask &= keep_ref[0] > 0                 # (1, bk) row
            s = jnp.where(mask, s, _NEG)

            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None]) * mask.astype(jnp.float32)
            l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
            acc_scr[...] = (acc_scr[...] * corr[:, None]
                            + jnp.dot(p, v,
                                      preferred_element_type=jnp.float32))
            m_scr[...] = m_new

        @pl.when(ik == nk - 1)
        def _finalize():
            l = l_scr[...]
            safe = jnp.where(l > 0, l, 1.0)
            o_ref[0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    kv_keep: Optional[jax.Array] = None,
                    q_pos: Optional[jax.Array] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, H, Lq, Dh); k, v: (B, H, Lk, Dh) or GQA-grouped
    (B, KV, Lk, Dh) with H % KV == 0 -- grouped K/V is read through the
    BlockSpec index map (head h -> group h // G), never materialized
    H-wide.  kv_keep: optional (B, H, Lk) bool (per *query* head -- SPLS
    prunes per head).  q_pos: optional (B, H, Lq) int32 original position
    of each query row (for SPLS-packed rows); defaults to arange semantics
    when omitted.  Ragged Lq/Lk are padded internally.  ``interpret=None``
    interprets on CPU only (:func:`repro.kernels.interpret.resolve_interpret`).

    q_pos rides in as a ``(B*H, Lq, 1)`` column and kv_keep as a
    ``(B*H, 1, Lk)`` row, so each block's last two dims are either a
    multiple of the TPU's (8, 128) tile or the whole array dim."""
    B, H, Lq, Dh = q.shape
    KVh, Lk = k.shape[1], k.shape[2]
    assert H % KVh == 0, (H, KVh)
    G = H // KVh
    bq, bk = min(block_q, Lq), min(block_k, Lk)
    pad_q, pad_k = (-Lq) % bq, (-Lk) % bk

    if pad_k and kv_keep is None:
        # the keep mask doubles as the padded-column kill switch
        kv_keep = jnp.ones((B, H, Lk), bool)
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        if q_pos is not None:
            # padded rows repeat the last real position (edge mode), so the
            # min/max over a q tile -- and with it block liveness -- is
            # exactly what the real rows imply; their outputs are sliced off
            q_pos = jnp.pad(q_pos, ((0, 0), (0, 0), (0, pad_q)),
                            mode="edge")
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        kv_keep = jnp.pad(kv_keep, ((0, 0), (0, 0), (0, pad_k)))
    Lqp, Lkp = Lq + pad_q, Lk + pad_k
    nq, nk = Lqp // bq, Lkp // bk
    scale = Dh ** -0.5

    # flat program id b = (batch * KV + kv) * G + g, so b // G addresses the
    # grouped K/V row -- GQA sharing via the index map, no repeated copies
    args = [q.reshape(B * H, Lqp, Dh),
            k.reshape(B * KVh, Lkp, Dh),
            v.reshape(B * KVh, Lkp, Dh)]
    in_specs = [
        pl.BlockSpec((1, bq, Dh), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, Dh), lambda b, i, j: (b // G, j, 0)),
        pl.BlockSpec((1, bk, Dh), lambda b, i, j: (b // G, j, 0)),
    ]
    if q_pos is not None:
        args.append(q_pos.reshape(B * H, Lqp, 1).astype(jnp.int32))
        in_specs.append(pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)))
    if kv_keep is not None:
        args.append(kv_keep.reshape(B * H, 1, Lkp).astype(jnp.int32))
        in_specs.append(pl.BlockSpec((1, 1, bk), lambda b, i, j: (b, 0, j)))

    kernel = _make_kernel(scale=scale, causal=causal, window=window,
                          softcap=softcap, bq=bq, bk=bk, nk=nk,
                          has_qpos=q_pos is not None,
                          has_keep=kv_keep is not None)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, Dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Lqp, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, Dh), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(*args)
    return out.reshape(B, H, Lqp, Dh)[:, :, :Lq]
