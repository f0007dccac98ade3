"""Pallas TPU kernel: single-token flash decode against a *paged* KV cache.

The serving engine (``repro.serving``) stores KV in a block pool of
fixed-size pages instead of one dense ``n_slots x max_len`` cache.  This
kernel is the decode path of that layout: one query token per sequence
attends over its pages, gathered through a per-sequence block table.

Layout (see ``src/repro/serving/README.md`` for the lifecycle):

  k_pages / v_pages: (L, KV, n_pages, page_size, Dh)  -- the model's
      stacked pool, one pool per layer; page 0 is the reserved *null page*
      (block-table filler; reads of it are always masked out).  A single
      layer's (KV, n_pages, page_size, Dh) pool is a stack of one.
  layer:             () int32                      -- the layer to read:
      the serving step's layer scan carries the stacked pool and passes its
      layer index, so no layer is ever sliced out of the pool.
  pos_pages:         (n_pages, page_size) int32    -- original token
      position of every written slot.  Once SPLS page pruning has compacted
      a sequence, slot index != token position, so sliding-window masks must
      consult these ids.
  tables:            (B, P) int32                  -- block tables (physical
      page id per logical page); unallocated entries hold the null page.
  kv_len:            (B,) int32                    -- written slots per row.
  pos:               (B,) int32                    -- original position of
      the current query token (inclusive upper bound of the window).

Grid: (B,), one step per sequence with all of its KV heads.  The pools
stay in HBM (``memory_space=pl.ANY``); the block table, lengths,
positions and the layer index ride in as scalar-prefetch operands.
Inside a step a loop walks the sequence in blocks of
``pages_per_block(P)`` pages and runs ``ceil(kv_len / (pages_per_block *
page_size))`` times, so the work follows the live length, not ``P``.
Each block's pages are copied with ``pltpu.make_async_copy`` -- one copy
per page, all KV heads at once, from ``[layer, :, page]`` with the
physical page read from the block table -- into one half of a
double-buffered VMEM block, and the next block's copies start before the
current block is computed.  Pages past ``kv_len`` are never copied; the
partial last block is masked by slot index, and its rows that no copy
filled are zeroed before P.V.  The online-softmax recurrence is the same
as ``flash_decode`` and runs in float32.  With a window, the rows'
original ids (``pos_pages`` gathered through the tables) mask every slot,
and a block whose written slots all fell out of the window is skipped
whole.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve_interpret

__all__ = ["paged_flash_decode", "pages_per_block", "pages_visited",
           "NULL_PAGE"]

NULL_PAGE = 0
PAGES_PER_BLOCK = 8
_NEG = -1e30


def pages_per_block(pages_per_seq: int) -> int:
    """Pages the kernel copies and computes per loop step: a block of
    ``PAGES_PER_BLOCK`` pages, or the whole table when it is shorter."""
    return min(PAGES_PER_BLOCK, pages_per_seq)


def pages_visited(n_valid, page_size: int) -> int:
    """Pages the kernel copies for rows attending over ``n_valid`` slots
    (an int or an array of per-row counts): the same arithmetic as its
    loop, which copies the pages holding slots ``0 .. n_valid - 1`` and
    nothing past them.  A row the engine leaves inactive attends over one
    slot (of the null page), so it counts one page."""
    n = np.asarray(n_valid, np.int64)
    return int((-(-n // page_size)).sum())


def _kernel(bt_ref, kl_ref, cp_ref, ly_ref, q_ref, k_hbm, v_hbm, *rest,
            scale, softcap, window, ps, ppb, kv, np_):
    if window is None:
        pp_ref = None
        o_ref, k_buf, v_buf, sem, m_scr, l_scr, acc_scr = rest
    else:
        pp_ref, o_ref, k_buf, v_buf, sem, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    layer = ly_ref[0]
    bk = ppb * ps
    n_valid = jnp.minimum(kl_ref[b], np_ * ps)
    n_blk = (n_valid + bk - 1) // bk

    def copies(blk, slot, p):
        page = bt_ref[b, blk * ppb + p]
        dst = pl.ds(p * ps, ps)
        return (pltpu.make_async_copy(k_hbm.at[layer, :, page],
                                      k_buf.at[slot, :, dst], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, :, page],
                                      v_buf.at[slot, :, dst], sem.at[1, slot]))

    def each_live_page(blk, slot, fn):
        # only pages that hold a written slot are copied (and waited for)
        for p in range(ppb):
            @pl.when((blk * ppb + p) * ps < n_valid)
            def _():
                for c in copies(blk, slot, p):
                    fn(c)

    @pl.when(n_blk > 0)
    def _first():
        each_live_page(0, 0, lambda c: c.start())

    m_scr[...] = jnp.full_like(m_scr, _NEG)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blk)
        def _prefetch():
            each_live_page(blk + 1, 1 - slot, lambda c: c.start())

        each_live_page(blk, slot, lambda c: c.wait())
        slot0 = blk * bk
        row = slot0 + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = row < n_valid                                # (1, bk)
        if window is not None:
            mask &= cp_ref[b] - pp_ref[0, pl.ds(blk, 1), :] < window
        # rows past n_valid hold whatever the buffer held: zero them so a
        # masked (zero) weight never meets a NaN
        vrow = (slot0 + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
                < n_valid)

        def compute():
            for h in range(kv):
                q = q_ref[0, h].astype(jnp.float32)             # (G, Dh)
                k = k_buf[slot, h].astype(jnp.float32)          # (bk, Dh)
                v = jnp.where(vrow, v_buf[slot, h].astype(jnp.float32), 0.0)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale  # (G, bk)
                if softcap is not None:
                    s = jnp.tanh(s / softcap) * softcap
                s = jnp.where(mask, s, _NEG)
                m_prev = m_scr[h]                                # (G, 1)
                m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
                corr = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
                l_scr[h] = l_scr[h] * corr + p.sum(-1, keepdims=True)
                acc_scr[h] = acc_scr[h] * corr + jnp.dot(
                    p, v, preferred_element_type=jnp.float32)
                m_scr[h] = m_new

        if window is None:
            compute()
        else:
            # block-level window skip: every written slot aged out
            pl.when(jnp.any(mask))(compute)
        return carry

    jax.lax.fori_loop(0, n_blk, body, 0)
    for h in range(kv):
        l = l_scr[h]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, h] = (acc_scr[h] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "window", "interpret"))
def paged_flash_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       pos_pages: jax.Array, tables: jax.Array,
                       kv_len: jax.Array, pos: jax.Array,
                       layer: Optional[jax.Array] = None,
                       softcap: Optional[float] = None,
                       window: Optional[int] = None,
                       interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, KV, G, Dh) one token per sequence; k/v_pages: the stacked
    (L, KV, N, ps, Dh) pool read at ``layer`` (a traced () int), or one
    layer's (KV, N, ps, Dh) pool with ``layer=None``; pos_pages: (N, ps);
    tables: (B, P); kv_len/pos: (B,).  Returns (B, KV, G, Dh).
    ``pos_pages`` is read only with a window: gathered through the tables
    into one ``(n_blocks, block)`` id row per sequence, so each block's ids
    are one sublane of it.  ``interpret=None`` interprets on CPU only."""
    if (layer is None) != (k_pages.ndim == 4):
        raise ValueError("a stacked (L, KV, N, ps, Dh) pool takes a layer "
                         "index; one layer's (KV, N, ps, Dh) pool takes none")
    if layer is None:  # one layer: a stack of one
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    B, KV, G, Dh = q.shape
    ps = k_pages.shape[3]
    P = tables.shape[1]
    ppb = pages_per_block(P)
    bk = ppb * ps
    tables = tables.astype(jnp.int32)
    kv_len = kv_len.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    in_specs = [
        pl.BlockSpec((1, KV, G, Dh), lambda b, bt, kl, cp, ly: (b, 0, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [q, k_pages, v_pages]
    if window is not None:
        n_blk = -(-P // ppb)
        ids = pos_pages.astype(jnp.int32)[tables].reshape(B, P * ps)
        ids = jnp.pad(ids, ((0, 0), (0, n_blk * bk - P * ps)))
        operands.append(ids.reshape(B, n_blk, bk))
        in_specs.append(pl.BlockSpec((1, n_blk, bk),
                                     lambda b, bt, kl, cp, ly: (b, 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, G, Dh),
                               lambda b, bt, kl, cp, ly: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, KV, bk, Dh), k_pages.dtype),
            pltpu.VMEM((2, KV, bk, Dh), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, Dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=Dh ** -0.5, softcap=softcap,
                          window=window, ps=ps, ppb=ppb, kv=KV, np_=P),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=resolve_interpret(interpret),
        name="paged_flash_decode",
    )(tables, kv_len, pos, layer, *operands)
