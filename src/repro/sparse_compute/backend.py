"""Compute-backend registry: how token-compacted linear ops execute.

Mirrors the attention backend registry (:mod:`repro.models.attn_backend`)
for the *linear* ops that SPLS sparsifies -- QKV projection and the FFN.
Every backend provides the same two primitives:

    gathered_matmul(x, w, perm, src_slot=None)  ->  (C, F) or (M, F)
    gather_rows(rows, idx)                      ->  rows[..., idx, :]

with ``x: (L, D)`` source rows, ``perm: (C,)`` packed row indices, and
``src_slot: (M,)`` the packed slot each output row reads (the leader
broadcast).

  * ``dense``         -- compute every row, gather afterwards: the
    simulation-mode semantics (zero compute saving; the numerics oracle).
  * ``packed_xla``    -- XLA ``pack_by_mask``-style execution: gather the
    packed rows, matmul at the reduced size, scatter through the leader
    map.  Equal to ``dense`` up to float32 summation order (a row
    subset of a dot need not sum in the full dot's order) whenever
    capacity covers every critical row.
  * ``packed_pallas`` -- :mod:`repro.kernels.gathered_matmul`: the gather
    rides in the matmul's DMA schedule (scalar-prefetched row indices,
    per-row async copies into the VMEM panel) and the leader scatter is a
    BlockSpec-index-map gather.  Compiled on an accelerator, interpreted
    on a CPU host (bit-accurate, slow).

``"auto"`` resolves from the platform and whether a sparsity plan exists;
the ``dense`` default keeps every existing path byte-identical until a
caller opts in.
"""

from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["AUTO", "DENSE", "register_compute_backend",
           "get_compute_backend", "available_compute_backends",
           "resolve_compute_backend", "is_packed"]

AUTO = "auto"
DENSE = "dense"


class _ComputeBackend(NamedTuple):
    gathered_matmul: Callable
    gather_rows: Callable
    doc: str


_REGISTRY: Dict[str, _ComputeBackend] = {}


def register_compute_backend(name: str, gathered_matmul: Callable,
                             gather_rows: Callable, doc: str = "") -> None:
    _REGISTRY[name] = _ComputeBackend(gathered_matmul, gather_rows, doc)


def available_compute_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_compute_backend(name: str) -> _ComputeBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown compute backend {name!r}; "
            f"registered: {available_compute_backends()}") from None


def is_packed(name: Optional[str]) -> bool:
    """True for backends that actually shrink the computed row count."""
    return name in ("packed_xla", "packed_pallas")


def resolve_compute_backend(name: Optional[str], *, sparse: bool,
                            platform: Optional[str] = None) -> str:
    """Map a configured compute-backend name (possibly ``"auto"``/None) to
    a concrete registry key.

    ``auto``: without a sparsity plan there is nothing to pack ->
    ``dense``; with one, the Pallas fusion on TPU and the XLA pack/unpack
    path elsewhere.  Packed backends without SPLS are a configuration
    error (there is no critical-row structure to pack by) and raise.
    """
    name = name or AUTO
    if name == AUTO:
        if not sparse:
            return DENSE
        platform = platform or jax.default_backend()
        return "packed_pallas" if platform == "tpu" else "packed_xla"
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown compute backend {name!r}; "
            f"registered: {available_compute_backends()}")
    if is_packed(name) and not sparse:
        raise ValueError(
            f"compute backend {name!r} packs SPLS critical rows, but SPLS "
            f"is disabled (spls.enabled=False): there is no sparsity plan "
            f"to pack by -- use 'dense' or enable SPLS")
    return name


# ---------------------------------------------------------------------------
# registrations
# ---------------------------------------------------------------------------

def _xla_gather_rows(rows: jax.Array, idx: jax.Array) -> jax.Array:
    return jnp.take(rows, idx, axis=0)


def _dense_gathered_matmul(x: jax.Array, w: jax.Array, perm: jax.Array,
                           src_slot: Optional[jax.Array] = None) -> jax.Array:
    # simulation-mode semantics: every row computed, results gathered
    full = jnp.einsum("ld,df->lf", x, w)
    out = jnp.take(full, perm, axis=0)
    return out if src_slot is None else jnp.take(out, src_slot, axis=0)


def _packed_xla_gathered_matmul(x: jax.Array, w: jax.Array, perm: jax.Array,
                                src_slot: Optional[jax.Array] = None
                                ) -> jax.Array:
    out = jnp.einsum("cd,df->cf", jnp.take(x, perm, axis=0), w)
    return out if src_slot is None else jnp.take(out, src_slot, axis=0)


def _packed_pallas_gathered_matmul(x: jax.Array, w: jax.Array,
                                   perm: jax.Array,
                                   src_slot: Optional[jax.Array] = None
                                   ) -> jax.Array:
    from repro.kernels.gathered_matmul import gathered_matmul

    # the kernel accumulates in float32; round to the operands' result
    # dtype like the XLA backends' einsum does, so every backend hands
    # the next op the same dtype (bf16 serving rounds at the same point)
    return gathered_matmul(x, w, perm, src_slot=src_slot).astype(
        jnp.result_type(x, w))


def _packed_pallas_gather_rows(rows: jax.Array, idx: jax.Array) -> jax.Array:
    from repro.kernels.gathered_matmul import gather_rows_kernel

    return gather_rows_kernel(rows, idx)


register_compute_backend(
    DENSE, _dense_gathered_matmul, _xla_gather_rows,
    doc="compute every row, gather afterwards (simulation-mode oracle)")
register_compute_backend(
    "packed_xla", _packed_xla_gathered_matmul, _xla_gather_rows,
    doc="XLA gather -> reduced matmul -> leader scatter")
register_compute_backend(
    "packed_pallas", _packed_pallas_gathered_matmul,
    _packed_pallas_gather_rows,
    doc="Pallas fused gather/matmul; scatter as BlockSpec index-map DMA")
