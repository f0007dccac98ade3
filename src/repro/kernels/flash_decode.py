"""Pallas TPU kernel: single-token flash decode against a *contiguous*
(slot-per-request) KV cache.

Decode cells (decode_32k / long_500k) are memory-bound: one query token
reads the whole KV cache.  The kernel streams the cache through VMEM in
``bk`` chunks with the online-softmax recurrence, honouring the write
position (`pos`) and an optional sliding window -- SWA decodes touch only
``window`` positions, which is what makes h2o/gemma2 long_500k cells
sub-quadratic in practice.

For the block-pool *paged* variant (per-request block tables over a shared
page pool, as used by ``repro.serving``) see
``repro.kernels.paged_decode.paged_flash_decode``.

Grid: (B*KV, S/bk); one program row per (batch, kv-head); the G query
heads of the group are carried together in the q tile (they share the K/V
reads -- the whole point of GQA at decode time).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve_interpret

__all__ = ["flash_decode"]

_NEG = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, softcap, window, bk, nk):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[pl.program_id(0)]
    k_start = ik * bk
    live = k_start <= pos
    if window is not None:
        live = jnp.logical_and(live, k_start + bk - 1 > pos - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (G, Dh)
        k = k_ref[0].astype(jnp.float32)          # (bk, Dh)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        j = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = j <= pos
        if window is not None:
            mask &= pos - j < window
        s = jnp.where(mask, s, _NEG)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None]) * mask.astype(jnp.float32)
        l_scr[...] = l_scr[...] * corr + p.sum(-1)
        acc_scr[...] = (acc_scr[...] * corr[:, None]
                        + jnp.dot(p, v, preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "window", "block_k",
                                             "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, pos: jax.Array,
                 softcap: Optional[float] = None,
                 window: Optional[int] = None, block_k: int = 512,
                 interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, KV, G, Dh) one token per row; k/v: (B, KV, S, Dh) caches;
    pos: (B,) current write index (inclusive), a scalar-prefetch operand.
    Returns (B, KV, G, Dh).  ``interpret=None`` interprets on CPU only."""
    B, KV, G, Dh = q.shape
    S = k.shape[2]
    bk = min(block_k, S)
    assert S % bk == 0
    nk = S // bk
    scale = Dh ** -0.5

    qf = q.reshape(B * KV, G, Dh)
    kf = k.reshape(B * KV, S, Dh)
    vf = v.reshape(B * KV, S, Dh)
    posf = jnp.repeat(pos.astype(jnp.int32), KV)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * KV, nk),
        in_specs=[
            pl.BlockSpec((1, G, Dh), lambda b, j, p: (b, 0, 0)),
            pl.BlockSpec((1, bk, Dh), lambda b, j, p: (b, j, 0)),
            pl.BlockSpec((1, bk, Dh), lambda b, j, p: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, Dh), lambda b, j, p: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, softcap=softcap,
                          window=window, bk=bk, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KV, G, Dh), q.dtype),
        interpret=resolve_interpret(interpret),
    )(posf, qf, kf, vf)
    return out.reshape(B, KV, G, Dh)
