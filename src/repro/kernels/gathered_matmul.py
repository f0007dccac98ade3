"""Pallas TPU kernel: fused gather-by-row-index -> MXU matmul.

The TPU-native realization of ESACT's dynamic-allocation compute (Sec.
IV-D) for the *linear* ops: capacity-mode SPLS packs critical token rows
to a static capacity ``C`` and computes the QKV / FFN matmuls only on
those rows.  Done naively in XLA that is two passes over HBM -- gather a
``(C, D)`` copy of the rows, then matmul it -- so this kernel fuses the
gather into the matmul's DMA schedule, the same move ``paged_decode``
makes for the block table:

* the packed row indices (``perm``) ride in as a **scalar-prefetch
  operand**;
* each grid step's row panel is brought into VMEM by **per-row async
  copies** resolved against ``perm`` (the gather happens in the DMA
  schedule; no ``(C, D)`` gathered copy ever lands in HBM), pipelined
  two-deep over a pair of DMA semaphores so row ``r + 1``'s copy is in
  flight while row ``r``'s is awaited.  Source and panel are ``(rows, 1,
  D)`` views: a one-row slice of a 2-D ref breaks the TPU's 8-row sublane
  tiling, while a leading-dim index of a 3-D ref copies one whole
  ``(1, D)`` tile;
* the MXU consumes the panel directly (K-slices of the VMEM panel), and
  the output tile accumulates across K steps exactly like
  ``hlog_qmatmul``.

The leader-scatter that recovers full-length outputs
(``out[row] = packed[src_slot[row]]``) is the same pattern with the
index on the *input* BlockSpec: :func:`gather_rows_kernel` resolves each
output row's source slot in the index map, so the scatter is also pure
DMA scheduling.  :func:`gathered_matmul` chains both when ``src_slot``
is given -- gather -> matmul -> leader-scatter in one call.

Numerics: the panel is float32 and, with ``bk=None`` (the default),
the whole contraction runs in one dot per tile.  The result equals the
XLA ``x[perm] @ w`` oracle up to float32 summation order: a tile's dot
need not sum in the order of the full dot (XLA's CPU dot does not
either), so equality is exact only where the arithmetic is (e.g.
small-integer data).  ``bk`` blocks the contraction for a smaller VMEM
footprint.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve_interpret

__all__ = ["gathered_matmul", "gather_rows_kernel"]


def _gmm_kernel(perm_ref, x_hbm, w_ref, o_ref, xs, sem, *, bm, bk,
                double_buffer):
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((j == 0) & (k == 0))
    def _gather():
        # per-row DMA gather of this tile's source rows into the VMEM
        # panel: the row index comes from the scalar-prefetch operand, so
        # the gather is part of the DMA schedule (cf. paged_decode's
        # block-table index maps, which gather at page granularity).
        # Double-buffered: row r+1's copy is issued before row r is
        # awaited, so at steady state one DMA is always in flight behind
        # the one being waited on (start/wait alternate between the two
        # DMA semaphores; each row lands directly in its own panel slot,
        # so only the semaphores rotate -- no staging copy).  Bitwise
        # identical to the serialized gather: destinations are disjoint
        # and the panel is fully awaited before the MXU reads it.
        def dma(r, slot):
            src = perm_ref[i * bm + r]
            # (1, D) tile of the (L, 1, D) source into panel row r
            return pltpu.make_async_copy(x_hbm.at[src], xs.at[r],
                                         sem.at[slot])

        if double_buffer:
            dma(0, 0).start()

            def body(r, carry):
                @pl.when(r + 1 < bm)
                def _start_next():
                    dma(r + 1, (r + 1) % 2).start()

                dma(r, r % 2).wait()
                return carry

            jax.lax.fori_loop(0, bm, body, 0)
        else:
            # serialized baseline (bench_kernels times it against the
            # buffered schedule): each row's copy fully completes before
            # the next is issued, so no DMA is ever in flight behind a
            # wait -- same destinations, bitwise-identical panel
            def body_serial(r, carry):
                d = dma(r, 0)
                d.start()
                d.wait()
                return carry

            jax.lax.fori_loop(0, bm, body_serial, 0)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xt = xs[:, 0, pl.ds(pl.multiple_of(k * bk, bk), bk)]
    o_ref[...] += jnp.dot(xt, w_ref[...], preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret",
                                    "double_buffer"))
def _gathered_matmul_padded(x: jax.Array, w: jax.Array, perm: jax.Array,
                            bm: int, bn: int, bk: int,
                            interpret: bool,
                            double_buffer: bool = True) -> jax.Array:
    C = perm.shape[0]
    L, D = x.shape
    _, F = w.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(C // bm, F // bn, D // bk),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),    # x (L, 1, D) in HBM
            pl.BlockSpec((bk, bn), lambda i, j, k, perm: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, perm: (i, j)),
        scratch_shapes=[
            pltpu.VMEM((bm, 1, D), jnp.float32),     # gathered panel
            pltpu.SemaphoreType.DMA((2,)),      # double-buffered row copies
        ],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, bm=bm, bk=bk,
                          double_buffer=double_buffer),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, F), jnp.float32),
        interpret=interpret,
    )(perm, x.reshape(L, 1, D), w)


def gathered_matmul(x: jax.Array, w: jax.Array, perm: jax.Array,
                    src_slot: Optional[jax.Array] = None,
                    bm: int = 128, bn: int = 128, bk: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    double_buffer: bool = True) -> jax.Array:
    """``x[perm] @ w`` with the gather fused into the matmul DMA schedule.

    x: (L, D) source rows; w: (D, F); perm: (C,) int32 packed row indices
    (may repeat; out-of-pack slots typically carry harmless filler rows).
    Returns (C, F) float32 -- or, with ``src_slot`` (M,) given, the
    leader-scattered (M, F) ``out[r] = (x[perm] @ w)[src_slot[r]]``
    (:func:`gather_rows_kernel` as the epilogue, still no XLA gather).

    Ragged C / F are padded internally (padded perm slots gather row 0,
    computed wastefully and sliced off -- the same discipline as the
    capacity pack).  ``bk=None`` runs the whole contraction per tile;
    see the module docstring for numerics.

    ``double_buffer=False`` serializes the row gather (start+wait per
    row, no overlap) -- bitwise identical, kept as the timing baseline
    that isolates what the two-semaphore pipeline buys
    (``benchmarks/bench_kernels.py`` times both; the dispatch carries a
    ``jax.profiler.TraceAnnotation`` so on-TPU profiles name the
    variant).  ``interpret=None`` interprets on CPU only.
    """
    interpret = resolve_interpret(interpret)
    L, D = x.shape
    D2, F = w.shape
    assert D == D2, (x.shape, w.shape)
    C = perm.shape[0]
    bm = min(bm, C)
    bn = min(bn, F)
    bk = D if bk is None else min(bk, D)
    assert D % bk == 0, f"contraction {D} not tileable by bk={bk}"
    pc = (-C) % bm
    if pc:
        perm = jnp.pad(perm, (0, pc))
    pf = (-F) % bn
    if pf:
        w = jnp.pad(w, ((0, 0), (0, pf)))
    # named profiler annotation: on-TPU traces (and Perfetto exports of
    # jax.profiler captures) attribute the dispatch to the exact gather
    # schedule being measured
    variant = "buffered" if double_buffer else "serialized"
    with jax.profiler.TraceAnnotation(f"gathered_matmul/{variant}"):
        out = _gathered_matmul_padded(x.astype(jnp.float32),
                                      w.astype(jnp.float32),
                                      perm.astype(jnp.int32),
                                      bm, bn, bk, interpret,
                                      double_buffer=double_buffer)
    out = out[:C, :F]
    if src_slot is not None:
        out = gather_rows_kernel(out, src_slot, interpret=interpret)
    return out


def _gather_kernel(idx_ref, src_ref, o_ref):
    o_ref[...] = src_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows_kernel(src: jax.Array, idx: jax.Array,
                       interpret: Optional[bool] = None) -> jax.Array:
    """``out[i] = src[idx[i]]`` -- the leader-scatter as pure DMA.

    src: (C, F); idx: (M,) int32 source row per output row.  The index
    rides in as a scalar-prefetch operand and each output row's source is
    resolved by the input BlockSpec index map, so the whole scatter is
    realised in the DMA schedule (no gathered intermediate, no XLA
    gather op) -- the row-granular version of ``paged_decode``'s
    block-table lookup.  Rows move as ``(1, 1, F)`` blocks of ``(rows,
    1, F)`` views, whose last two dims are whole-array (the TPU block
    rule).  ``interpret=None`` interprets on CPU only.
    """
    C, F = src.shape
    M = idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M,),
        in_specs=[pl.BlockSpec((1, 1, F), lambda i, idx: (idx[i], 0, 0))],
        out_specs=pl.BlockSpec((1, 1, F), lambda i, idx: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, 1, F), src.dtype),
        interpret=resolve_interpret(interpret),
    )(idx.astype(jnp.int32), src.reshape(C, 1, F))
    return out.reshape(M, F)
