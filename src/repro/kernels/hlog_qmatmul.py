"""Pallas TPU kernel: fused HLog projection + prediction matmul.

The ASIC's bit-level prediction unit (Sec. IV-B) performs HLog quantization
with a shift detector and replaces the multiplies of the prediction matmul
with exponent additions.  A TPU has no scalar shift-add datapath that can
beat the MXU, so the TPU-native adaptation (DESIGN.md) fuses the *numerics*:
the HLog projection of both operands happens in VMEM registers (VPU, a few
float ops per element -- cheaper than an HBM round-trip for a quantized
copy) immediately followed by the MXU matmul of the projected tiles.  The
win vs. the naive pipeline is one fused pass instead of
project -> materialize -> matmul, i.e. 2x fewer HBM reads of X/W.

Grid: (M/bm, N/bn, K/bk) with K innermost; the output tile is revisited and
accumulated across K steps (initialised at k == 0).  All tiles live in VMEM
via BlockSpec; bm/bn/bk default to MXU-aligned 128 multiples.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .interpret import resolve_interpret

__all__ = ["hlog_qmatmul"]


def _hlog_project_inkernel(q: jax.Array) -> jax.Array:
    """Branch-free HLog projection of integer-valued floats (VPU ops).

    mag = |q| = 2^m * r with r in [1, 2):
      r < 1.25 -> 2^m ; 1.25 <= r < 1.75 -> 1.5 * 2^m ; r >= 1.75 -> 2^{m+1}
    Ties already round up because the comparisons are `<`.  Exact for the
    int8 grid (see tests vs. the bit-level encoder).
    """
    mag = jnp.abs(q)
    safe = jnp.maximum(mag, 1.0)
    m = jnp.floor(jnp.log2(safe))
    p = jnp.exp2(m)
    r = safe / p
    lvl = jnp.where(r < 1.25, p, jnp.where(r < 1.75, 1.5 * p, 2.0 * p))
    return jnp.where(mag == 0, 0.0, jnp.sign(q) * lvl)


def _kernel(x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xt = _hlog_project_inkernel(x_ref[...].astype(jnp.float32))
    wt = _hlog_project_inkernel(w_ref[...].astype(jnp.float32))
    o_ref[...] += jnp.dot(xt, wt, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def hlog_qmatmul(xq: jax.Array, wq: jax.Array, bm: int = 128, bn: int = 128,
                 bk: int = 128,
                 interpret: Optional[bool] = None) -> jax.Array:
    """hlog(xq) @ hlog(wq).  xq: (M, K); wq: (K, N); int-valued float32.
    ``interpret=None`` interprets on CPU only.

    Shapes must tile evenly (callers pad); VMEM per step is
    ``bm*bk + bk*bn + bm*bn`` floats (default 192 KiB), well inside the
    ~16 MiB v5e VMEM even with double buffering.
    """
    M, K = xq.shape
    K2, N = wq.shape
    assert K == K2, (xq.shape, wq.shape)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, \
        f"({M},{K})x({K},{N}) not tileable by ({bm},{bn},{bk})"

    return pl.pallas_call(
        _kernel,
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(xq, wq)
