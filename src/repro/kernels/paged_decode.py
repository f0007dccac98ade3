"""Pallas TPU kernel: single-token flash decode against a *paged* KV cache.

The serving engine (``repro.serving``) stores KV in a block pool of
fixed-size pages instead of one dense ``n_slots x max_len`` cache.  This
kernel is the decode path of that layout: one query token per sequence
attends over its pages, gathered through a per-sequence block table.

Layout (see ``src/repro/serving/README.md`` for the lifecycle):

  k_pages / v_pages: (KV, n_pages, page_size, Dh)  -- the shared pool; page
      0 is the reserved *null page* (block-table filler / write sink for
      inactive batch rows; reads of it are always masked out).
  pos_pages:         (n_pages, page_size) int32    -- original token
      position of every written slot.  Once SPLS page pruning has compacted
      a sequence, slot index != token position, so sliding-window masks must
      consult these ids.
  tables:            (B, P) int32                  -- block tables (physical
      page id per logical page); unallocated entries hold the null page.
  kv_len:            (B,) int32                    -- written slots per row.
  pos:               (B,) int32                    -- original position of
      the current query token (inclusive upper bound of the window).

Grid: (B*KV, P).  The block table, lengths, and positions ride in as
scalar-prefetch operands, so each grid step's BlockSpec index map resolves
the *physical* page to bring into VMEM -- the gather happens in the DMA
schedule and no contiguous cache is ever materialized.  The online-softmax
recurrence is the same as ``flash_decode``; pages past ``kv_len`` (and, with
a window, pages whose slots all fell out of the window) are skipped whole.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve_interpret

__all__ = ["paged_flash_decode", "NULL_PAGE"]

NULL_PAGE = 0
_NEG = -1e30


def _kernel(bt_ref, kl_ref, cp_ref, q_ref, k_ref, v_ref, pp_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale, softcap, window, ps, kv, np_):
    i = pl.program_id(0)
    j = pl.program_id(1)
    b = i // kv

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    n_valid = kl_ref[b]
    slot0 = j * ps
    live = slot0 < n_valid
    if window is not None:
        # page-level window skip: a page is dead once every *written* slot
        # has aged out of the window (original ids, not slot indices)
        sl = slot0 + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        in_w = (sl < n_valid) & (cp_ref[b] - pp_ref[0] < window)
        live = jnp.logical_and(live, in_w.any())

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (G, Dh)
        k = k_ref[0, 0].astype(jnp.float32)       # (ps, Dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        slot = slot0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = slot < n_valid
        if window is not None:
            mask &= cp_ref[b] - pp_ref[0] < window      # pp: (1, ps) ids
        s = jnp.where(mask, s, _NEG)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None]) * mask.astype(jnp.float32)
        l_scr[...] = l_scr[...] * corr + p.sum(-1)
        acc_scr[...] = (acc_scr[...] * corr[:, None]
                        + jnp.dot(p, v, preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(j == np_ - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "window", "interpret"))
def paged_flash_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       pos_pages: jax.Array, tables: jax.Array,
                       kv_len: jax.Array, pos: jax.Array,
                       softcap: Optional[float] = None,
                       window: Optional[int] = None,
                       interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, KV, G, Dh) one token per sequence; k/v_pages: (KV, N, ps, Dh);
    pos_pages: (N, ps); tables: (B, P); kv_len/pos: (B,).
    Returns (B, KV, G, Dh).  ``pos_pages`` is read through an
    ``(N, 1, ps)`` view so each page's id row is a whole-array tile in its
    last two dims (the TPU block rule).  ``interpret=None`` interprets on
    CPU only."""
    B, KV, G, Dh = q.shape
    _, N, ps, _ = k_pages.shape
    P = tables.shape[1]
    scale = Dh ** -0.5
    qf = q.reshape(B * KV, G, Dh)
    tables = tables.astype(jnp.int32)
    kv_len = kv_len.astype(jnp.int32)
    pos = pos.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B * KV, P),
        in_specs=[
            pl.BlockSpec((1, G, Dh), lambda i, j, bt, kl, cp: (i, 0, 0)),
            pl.BlockSpec((1, 1, ps, Dh),
                         lambda i, j, bt, kl, cp: (i % KV, bt[i // KV, j],
                                                   0, 0)),
            pl.BlockSpec((1, 1, ps, Dh),
                         lambda i, j, bt, kl, cp: (i % KV, bt[i // KV, j],
                                                   0, 0)),
            pl.BlockSpec((1, 1, ps),
                         lambda i, j, bt, kl, cp: (bt[i // KV, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, Dh), lambda i, j, bt, kl, cp: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, softcap=softcap,
                          window=window, ps=ps, kv=KV, np_=P),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KV, G, Dh), q.dtype),
        interpret=resolve_interpret(interpret),
    )(tables, kv_len, pos, qf, k_pages, v_pages, pos_pages.reshape(N, 1, ps))
    return out.reshape(B, KV, G, Dh)
