"""Pallas TPU kernel: windowed pairwise L1 distances on the SPA.

The similarity unit of the accelerator compares the ``w`` rows of each local
window with L1 distance (Sec. III-B), costing L^2 (w-1) add/subs.  On TPU
the natural mapping is a reduction kernel: for each (batch*head, window) the
``w x Lk`` row tile streams through VMEM in ``bk`` column chunks and the
``w x w`` distance matrix accumulates in the revisited output block.

Grid: (B*H, L/w, Lk/bk), column chunks innermost.  VMEM per step is
``w * bk`` input floats plus the ``w*w`` accumulator -- tiny, so ``bk`` can
be large (2048 default) to amortise grid overhead.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .interpret import resolve_interpret

__all__ = ["local_similarity_dist"]


def _kernel(spa_ref, o_ref, *, w):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = spa_ref[0].astype(jnp.float32)            # (w, bk)
    d = jnp.abs(x[:, None, :] - x[None, :, :]).sum(-1)
    o_ref[0] += d


@functools.partial(jax.jit, static_argnames=("w", "bk", "interpret"))
def local_similarity_dist(spa: jax.Array, w: int = 8, bk: int = 2048,
                          interpret: Optional[bool] = None) -> jax.Array:
    """spa: (B, H, L, Lk) with L % w == 0 -> (B, H, L//w, w, w) L1 dists.
    ``interpret=None`` interprets on CPU only."""
    B, H, L, Lk = spa.shape
    assert L % w == 0, (L, w)
    nw = L // w
    bk = min(bk, Lk)
    assert Lk % bk == 0
    xf = spa.reshape(B * H * nw, w, Lk)

    out = pl.pallas_call(
        functools.partial(_kernel, w=w),
        grid=(B * H * nw, 1, Lk // bk),
        in_specs=[pl.BlockSpec((1, w, bk), lambda b, i, j: (b, 0, j))],
        out_specs=pl.BlockSpec((1, w, w), lambda b, i, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H * nw, w, w), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(xf)
    return out.reshape(B, H, nw, w, w)
