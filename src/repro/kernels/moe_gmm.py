"""Pallas TPU kernel: the grouped gated FFN of a mixture-of-experts layer.

Rows arrive grouped by expert: the caller lays the (token, expert) pairs
out so that every tile of ``bm`` rows belongs to one expert (a group's
last tile is padded), and names each tile's expert in ``tile_group``.
Both ride in as **scalar-prefetch operands**, so each grid step's weight
blocks are chosen by the index maps -- the expert switch is part of the
DMA schedule, the same move ``paged_decode`` makes for its block table.

Per tile the kernel runs the whole gated FFN of its expert,
``silu(x @ w_gate) * (x @ w_up) @ w_down``, blocked over the hidden width
``F`` in steps of ``bf`` and accumulated in float32 in the output block,
so the ``(rows, F)`` hidden activations never reach HBM.

Only the first ``n_tiles`` tiles are live; the grid is sized for the
worst case (every pair held here), which keeps the layer dropless with
static shapes.  A dead tile computes nothing, and its index maps repeat
the last live step's blocks, so it moves no data either: the kernel's
traffic follows the routed pairs, not the static bound.  Dead tiles'
output rows are left unwritten; the caller never reads them.

The pallas call is named ``moe_gmm``: a device trace labels its op so.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve_interpret

__all__ = ["moe_gmm", "tile_rows"]

_ACT = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}


def tile_rows(n_tokens: int, topk: int, n_experts: int) -> int:
    """Rows a tile holds: twice the pairs an expert gets on average
    (``n_tokens * topk / n_experts``), a power of two in [16, 256], so that
    a typical group fills one tile."""
    mean = max(1, n_tokens * topk // max(n_experts, 1))
    bm = 16
    while bm < 2 * mean and bm < 256:
        bm *= 2
    return bm


def _kernel(tg_ref, n_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, *, act):
    i = pl.program_id(0)
    f = pl.program_id(1)

    @pl.when(i < n_ref[0])
    def _live():
        @pl.when(f == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        x = x_ref[...]
        g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h = (_ACT[act](g) * u).astype(x.dtype)
        o_ref[...] += jnp.dot(h, wd_ref[...],
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bf", "act", "interpret"))
def moe_gmm(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
            w_down: jax.Array, tile_group: jax.Array, n_tiles: jax.Array,
            bm: int, bf: int = 256, act: str = "silu",
            interpret: Optional[bool] = None) -> jax.Array:
    """x: (NT * bm, D) rows, tile ``t`` all of expert ``tile_group[t]``;
    w_gate / w_up: (E, D, F); w_down: (E, F, D); tile_group: (NT,) int32,
    whose dead entries (``t >= n_tiles``) repeat the last live one;
    n_tiles: (1,) int32 live tiles.  Returns (NT * bm, D) float32; rows of
    dead tiles are unwritten.  ``interpret=None`` interprets on CPU only."""
    R, D = x.shape
    E, _, F = w_gate.shape
    NT = tile_group.shape[0]
    assert R == NT * bm, (R, NT, bm)
    bf = min(bf, F)
    assert F % bf == 0, f"hidden width {F} not tileable by bf={bf}"
    nf = F // bf

    def live(i, n):
        # a dead tile repeats the last live step's blocks (no DMA)
        return jnp.maximum(jnp.minimum(i, n[0] - 1), 0)

    def f_of(i, f, n):
        return jnp.where(i < n[0], f, nf - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(NT, nf),
        in_specs=[
            pl.BlockSpec((bm, D), lambda i, f, tg, n: (live(i, n), 0)),
            pl.BlockSpec((None, D, bf),
                         lambda i, f, tg, n: (tg[i], 0, f_of(i, f, n))),
            pl.BlockSpec((None, D, bf),
                         lambda i, f, tg, n: (tg[i], 0, f_of(i, f, n))),
            pl.BlockSpec((None, bf, D),
                         lambda i, f, tg, n: (tg[i], f_of(i, f, n), 0)),
        ],
        out_specs=pl.BlockSpec((bm, D), lambda i, f, tg, n: (live(i, n), 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, act=act),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="moe_gmm",
    )(tile_group.astype(jnp.int32), n_tiles.astype(jnp.int32), x,
      w_gate, w_up, w_down)
