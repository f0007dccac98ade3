"""Attention backend registry + dispatch.

Every way this repo can execute the attention mixer lives here, behind one
string-keyed registry, so the model, the serving engine, and the launch
steps all select the execution strategy the same way.  See
``src/repro/models/README.md`` for the selection rules and the semantics of
each backend.

Forward backends (full-sequence) all share one signature::

    fn(cfg, q, k, v, *, window, plan, q_capacity, kv_capacity) -> o

with ``q: (B, KV', G', L, Dh)``, ``k/v: (B, KV', L, Dh)`` in the head
layout produced by ``attention._project_qkv`` and ``o`` shaped like ``q``.

  * ``xla_dense``   -- materialized-scores softmax; with a plan, the
    simulation-mode SPLS semantics (:func:`spls_attention`): leader-row
    recovery + the full intra-row SPA mask.  The accuracy oracle.
  * ``xla_packed``  -- capacity-mode SPLS (:func:`spls_attention_packed`):
    critical rows / surviving columns packed to static capacities; real
    compute reduction with XLA static shapes.
  * ``xla_chunked`` -- KV-chunked online-softmax scan (flash recurrence in
    XLA); O(L * chunk) memory.  With a plan it runs
    :func:`spls_attention_chunked` (packed + chunked, index-based masks).
  * ``pallas_flash`` -- the Pallas kernel (``repro.kernels.flash_attention``)
    with the SPLS plan lowered to hardware-realizable block sparsity:
    ``kv_keep`` feeds the kernel's block-skip keep mask (dead K/V blocks are
    never computed -- the accelerator's zero-column pruning as structured
    block skips) and critical Q rows are packed to a block-rounded capacity
    via :func:`pack_by_mask`, carried through the kernel with their original
    positions (``q_pos``) and scattered back through the leader map.  The
    intra-row SPA top-k mask is intentionally *not* applied -- per-element
    masking is exactly the part a tiled MXU cannot skip; column + row
    sparsity is what the hardware realizes (cf. ``xla_chunked`` which shares
    these semantics and is the parity oracle under a plan).
    Runs compiled on an accelerator and interpreted on a CPU host
    (bit-accurate, slow; :mod:`repro.kernels.interpret`).

Decode backends share::

    fn(cfg, q, k, v, *, pos, window) -> o

with ``q: (B, KV, G, Dh)`` (one token), ``k/v: (B, KV, S, Dh)`` caches.

  * ``xla_dense_decode``    -- dense scores over the whole cache (XLA).
  * ``pallas_flash_decode`` -- ``repro.kernels.flash_decode`` streaming the
    cache through VMEM in chunks (position- and window-aware block skip).

Paged decode backends (the serving engine's block-pool KV cache,
``repro.serving``) share::

    fn(cfg, q, k_pages, v_pages, *, pos_pages, tables, kv_len, pos,
       window, layer=None) -> o

with ``q: (B, KV, G, Dh)``, ``k/v_pages`` the stacked ``(L, KV, N, ps,
Dh)`` page pools read at layer ``layer`` (a traced index), or one
layer's ``(KV, N, ps, Dh)`` pools with ``layer=None``, ``pos_pages: (N,
ps)`` original-position ids, ``tables: (B, P)`` block tables, ``kv_len:
(B,)`` written slots, ``pos: (B,)`` current original position.

  * ``xla_paged_decode``    -- XLA gather of the block table into a
    contiguous view, then dense masked scores.  The fallback / oracle.
  * ``pallas_paged_decode`` -- ``repro.kernels.paged_decode``: the block
    table rides in as a scalar-prefetch operand and each page is DMA'd by
    the BlockSpec index map (no contiguous gather is ever materialized).

``"auto"`` resolves per call site from platform, sequence length, and the
sparsity mode -- see :func:`resolve_backend`.
"""

from __future__ import annotations

import math
import warnings
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sparse_exec import (gather_rows, pack_by_mask,
                                    spls_attention, spls_attention_chunked,
                                    spls_attention_packed, unpack_by_leader)
from repro.core.spls import SparsityPlan
from repro.core.spls_chunked import ChunkedPlan
from .common import softcap as _softcap

__all__ = ["register_backend", "get_backend", "available_backends",
           "resolve_backend", "AUTO", "CHUNK_THRESHOLD", "KV_CHUNK"]

AUTO = "auto"
# Raise (instead of warn) when an explicitly configured backend has the
# wrong kind for a call site; see resolve_backend.  The per-call `strict`
# argument overrides this global default.
STRICT_BACKEND_KIND = False
_warned_kind_mismatch: set = set()
# KV-chunked attention kicks in above this length (keeps scores << O(L^2))
CHUNK_THRESHOLD = 8192
KV_CHUNK = 2048
# Pallas tile sizes (also the granularity of SPLS q packing / kv skipping)
PALLAS_BLOCK_Q = 128
PALLAS_BLOCK_K = 128


class _Backend(NamedTuple):
    fn: Callable
    decode: bool
    doc: str
    paged: bool = False


_REGISTRY: Dict[str, _Backend] = {}


def register_backend(name: str, decode: bool = False, paged: bool = False,
                     doc: str = "") -> Callable:
    """Decorator registering ``fn`` under ``name``; ``decode`` marks
    single-token backends, ``paged`` marks block-pool paged-cache backends
    (different signatures, see module docstring)."""

    def deco(fn: Callable) -> Callable:
        _REGISTRY[name] = _Backend(fn, decode, doc or (fn.__doc__ or ""),
                                   paged)
        return fn

    return deco


def available_backends(decode: Optional[bool] = None,
                       paged: Optional[bool] = None) -> Tuple[str, ...]:
    """Registered backend names, optionally filtered by decode/paged-ness."""
    return tuple(sorted(n for n, b in _REGISTRY.items()
                        if (decode is None or b.decode == decode)
                        and (paged is None or b.paged == paged)))


def get_backend(name: str) -> Callable:
    try:
        return _REGISTRY[name].fn
    except KeyError:
        raise ValueError(
            f"unknown attention backend {name!r}; "
            f"registered: {available_backends()}") from None


def _site_kind(decode: bool, paged: bool) -> str:
    return ("paged decode" if paged else "decode") if decode else "forward"


def resolve_backend(name: Optional[str], cfg, *, L: int, plan=None,
                    q_capacity: Optional[int] = None, decode: bool = False,
                    paged: bool = False,
                    platform: Optional[str] = None,
                    strict: Optional[bool] = None) -> str:
    """Map a configured backend name (possibly ``"auto"``/None) to a
    concrete registry key.

    An explicitly configured name whose kind does not match the call site
    (a forward name at a decode site, a dense decode name at a paged site,
    ...) falls back to that site's auto choice with a ``RuntimeWarning``
    (once per (name, site) pair), or raises when ``strict=True`` (per call)
    or :data:`STRICT_BACKEND_KIND` is set globally.

    The ``"auto"`` heuristic (documented in models/README.md):

    paged decode: TPU -> ``pallas_paged_decode``; else ``xla_paged_decode``.
    decode:   TPU -> ``pallas_flash_decode``; otherwise the inline dense
              decode path (``xla_dense``).
    forward:  1. ChunkedPlan (long-sequence progressive SPLS)
                 -> ``xla_chunked``  (the only consumer of index-based
                 packed chunking at O(Cq * chunk) memory);
              2. TPU -> ``pallas_flash`` (compiled kernel; with a plan the
                 hardware block-sparse lowering);
              3. plan + reduced q capacity -> ``xla_packed``;
              4. plan -> ``xla_dense`` (simulation-mode numerics);
              5. L > CHUNK_THRESHOLD -> ``xla_chunked``;
              6. otherwise -> ``xla_dense``.
    """
    name = name or AUTO
    if name != AUTO:
        b = _REGISTRY.get(name)
        if b is None:
            raise ValueError(
                f"unknown attention backend {name!r}; "
                f"registered: {available_backends()}")
        if b.decode == decode and b.paged == paged:
            return name
        # kind mismatch: the one config field drives every context, so a
        # name of the wrong kind for this site (forward at decode, dense
        # decode at a paged site, ...) falls through to the auto choice
        # for this site -- loudly, so a typo'd override cannot silently
        # serve through a different backend than the one asked for
        site = _site_kind(decode, paged)
        msg = (f"configured attention backend {name!r} is a "
               f"{_site_kind(b.decode, b.paged)} backend but this is a "
               f"{site} site; falling back to the auto choice for this "
               f"site (pass strict=True or set "
               f"repro.models.attn_backend.STRICT_BACKEND_KIND to raise)")
        if strict if strict is not None else STRICT_BACKEND_KIND:
            raise ValueError(msg)
        if (name, site) not in _warned_kind_mismatch:
            _warned_kind_mismatch.add((name, site))
            warnings.warn(msg, RuntimeWarning, stacklevel=2)
    platform = platform or jax.default_backend()
    if decode and paged:
        return ("pallas_paged_decode" if platform == "tpu"
                else "xla_paged_decode")
    if decode:
        return ("pallas_flash_decode" if platform == "tpu"
                else "xla_dense_decode")
    if isinstance(plan, ChunkedPlan):
        return "xla_chunked"
    if platform == "tpu":
        return "pallas_flash"
    if plan is not None:
        if q_capacity is not None and q_capacity < L:
            return "xla_packed"
        return "xla_dense"
    if L > CHUNK_THRESHOLD:
        return "xla_chunked"
    return "xla_dense"


# ---------------------------------------------------------------------------
# forward backends
# ---------------------------------------------------------------------------

def _band_mask(L: int, window: Optional[int], causal: bool) -> jax.Array:
    i = jnp.arange(L)[:, None]
    j = jnp.arange(L)[None, :]
    m = (j <= i) if causal else jnp.ones((L, L), bool)
    if window is not None:
        m = m & (i - j < window) & (j - i < (1 if causal else window))
    return m


def _broadcast_kv(q: jax.Array, k: jax.Array, v: jax.Array):
    B, KVp, Gp, L, Dh = q.shape
    kr = jnp.broadcast_to(k[:, :, None], (B, KVp, Gp, L, Dh))
    vr = jnp.broadcast_to(v[:, :, None], (B, KVp, Gp, L, Dh))
    return kr, vr


def _window_plan(plan: SparsityPlan, L: int, window: Optional[int],
                 causal: bool) -> SparsityPlan:
    """Intersect a block's sliding window into the plan's attention mask so
    SPLS + SWA keeps the same semantics on every backend (the Pallas and
    chunked paths window through position indices instead)."""
    if window is None:
        return plan
    return plan._replace(attn_mask=plan.attn_mask
                         & _band_mask(L, window, causal))


@register_backend("xla_dense",
                  doc="materialized scores; simulation-mode SPLS with plan")
def xla_dense(cfg, q, k, v, *, window=None, plan=None, q_capacity=None,
              kv_capacity=None) -> jax.Array:
    L, Dh = q.shape[-2], q.shape[-1]
    if plan is not None:
        kr, vr = _broadcast_kv(q, k, v)
        plan = _window_plan(plan, L, window, cfg.causal)
        return spls_attention(q, kr, vr, plan, Dh ** -0.5, cfg.attn_softcap)
    s = jnp.einsum("bkgqd,bkld->bkgql", q, k) * (Dh ** -0.5)
    s = _softcap(s, cfg.attn_softcap)
    m = _band_mask(L, window, cfg.causal)
    s = jnp.where(m, s, jnp.asarray(-1e30, s.dtype))
    a = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bkgql,bkld->bkgqd", a, v)


@register_backend("xla_packed",
                  doc="capacity-mode SPLS: pack critical rows/columns")
def xla_packed(cfg, q, k, v, *, window=None, plan=None, q_capacity=None,
               kv_capacity=None) -> jax.Array:
    if plan is None:  # nothing to pack -- degenerate to the dense scores
        return xla_dense(cfg, q, k, v, window=window)
    L, Dh = q.shape[-2], q.shape[-1]
    kr, vr = _broadcast_kv(q, k, v)
    plan = _window_plan(plan, L, window, cfg.causal)
    return spls_attention_packed(q, kr, vr, plan, q_capacity or L,
                                 kv_capacity or L, Dh ** -0.5,
                                 cfg.attn_softcap)


@register_backend("xla_chunked",
                  doc="KV-chunked online-softmax scan (flash in XLA)")
def xla_chunked(cfg, q, k, v, *, window=None, plan=None, q_capacity=None,
                kv_capacity=None) -> jax.Array:
    B, KVp, Gp, L, Dh = q.shape
    if plan is not None:
        # spls_attention_chunked pads ragged capacities to whole KV chunks
        # internally, so chunking (and O(Cq * chunk) memory) always holds
        return spls_attention_chunked(q, k, v, plan, q_capacity or L,
                                      min(kv_capacity or L, L),
                                      Dh ** -0.5, cfg.attn_softcap,
                                      kv_chunk=KV_CHUNK, causal=cfg.causal,
                                      window=window)

    C = min(KV_CHUNK, L)
    pad = (-L) % C
    if pad:  # ragged tail: padded columns are masked out by `kj < L`
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nC = (L + pad) // C
    scale = Dh ** -0.5
    qi = jnp.arange(L)

    def body(carry, ck):
        m_run, l_run, acc = carry
        k_c, v_c, c0 = ck
        s = jnp.einsum("bkgqd,bkld->bkgql", q, k_c).astype(jnp.float32) * scale
        s = _softcap(s, cfg.attn_softcap)
        kj = c0 + jnp.arange(C)
        mask = jnp.broadcast_to(kj[None, :] < L, (L, C))
        if cfg.causal:
            mask = mask & (kj[None, :] <= qi[:, None])
        if window is not None:
            mask = mask & (qi[:, None] - kj[None, :] < window)
            if not cfg.causal:
                mask = mask & (kj[None, :] - qi[:, None] < window)
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m_run, s.max(-1))
        corr = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new[..., None]) * mask.astype(jnp.float32)
        l_new = l_run * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgql,bkld->bkgqd", p.astype(v_c.dtype), v_c).astype(jnp.float32)
        return (m_new, l_new, acc), None

    kc = k.reshape(B, KVp, nC, C, Dh).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(B, KVp, nC, C, Dh).transpose(2, 0, 1, 3, 4)
    offs = jnp.arange(nC) * C
    init = (jnp.full((B, KVp, Gp, L), -1e30, jnp.float32),
            jnp.zeros((B, KVp, Gp, L), jnp.float32),
            jnp.zeros((B, KVp, Gp, L, Dh), jnp.float32))
    (m_f, l_f, acc), _ = jax.lax.scan(body, init, (kc, vc, offs))
    out = acc / jnp.maximum(l_f, 1e-9)[..., None]
    return out.astype(q.dtype)


@register_backend("pallas_flash",
                  doc="Pallas kernel; SPLS as block-skip + packed rows")
def pallas_flash(cfg, q, k, v, *, window=None, plan=None, q_capacity=None,
                 kv_capacity=None) -> jax.Array:
    from repro.kernels.flash_attention import flash_attention

    B, KVp, Gp, L, Dh = q.shape
    H = KVp * Gp
    qf = q.reshape(B, H, L, Dh)
    # k/v stay in the grouped (B, KV', L, Dh) layout: the kernel reads the
    # shared group K/V through its BlockSpec index map (no H-wide copy)
    kf, vf = k, v

    if plan is None:
        o = flash_attention(qf, kf, vf, causal=cfg.causal, window=window,
                            softcap=cfg.attn_softcap,
                            block_q=PALLAS_BLOCK_Q, block_k=PALLAS_BLOCK_K)
        return o.reshape(B, KVp, Gp, L, Dh)

    # SPLS plan -> hardware block sparsity:
    #  * kv_keep feeds the kernel keep mask (dead K blocks skipped whole);
    #  * critical Q rows packed to a block-rounded capacity, carried with
    #    their original positions, leader-recovered after the call.
    crit = plan.q_critical.reshape(B, H, L)
    keep = plan.kv_keep.reshape(B, H, L)
    leader = plan.q_leader.reshape(B, H, L)
    bq = min(PALLAS_BLOCK_Q, L)
    Cq = min(q_capacity or L, L)
    Cq = min(L, -(-Cq // bq) * bq)      # round capacity up to whole q blocks
    q_perm, q_slot = pack_by_mask(crit, Cq)
    qp = gather_rows(qf, q_perm)
    op = flash_attention(qp, kf, vf, causal=cfg.causal, window=window,
                         softcap=cfg.attn_softcap, kv_keep=keep,
                         q_pos=q_perm,
                         block_q=PALLAS_BLOCK_Q, block_k=PALLAS_BLOCK_K)
    o = unpack_by_leader(op, q_slot, leader)
    return o.reshape(B, KVp, Gp, L, Dh)


# ---------------------------------------------------------------------------
# decode backends
# ---------------------------------------------------------------------------

@register_backend("xla_dense_decode", decode=True,
                  doc="dense one-token decode over the whole cache")
def xla_dense_decode(cfg, q, k, v, *, pos, window=None) -> jax.Array:
    """q: (B, KV, G, Dh) one token; k/v: (B, KV, S, Dh); pos: (B,)."""
    S, Dh = k.shape[2], q.shape[-1]
    s = jnp.einsum("bkgd,bkld->bkgl", q, k) * (Dh ** -0.5)
    s = _softcap(s, cfg.attn_softcap)
    j = jnp.arange(S)[None, :]
    m = j <= pos[:, None]
    if window is not None:
        m = m & (pos[:, None] - j < window)
    s = jnp.where(m[:, None, None, :], s, jnp.asarray(-1e30, s.dtype))
    a = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bkgl,bkld->bkgd", a, v)


@register_backend("pallas_flash_decode", decode=True,
                  doc="Pallas decode kernel streaming the KV cache")
def pallas_flash_decode(cfg, q, k, v, *, pos, window=None) -> jax.Array:
    """q: (B, KV, G, Dh) one token; k/v: (B, KV, S, Dh); pos: (B,)."""
    from repro.kernels.flash_decode import flash_decode

    S = k.shape[2]
    bk = min(512, S)
    pad = (-S) % bk
    if pad:  # padded cache slots sit beyond `pos` -> masked by the kernel
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return flash_decode(q, k, v, pos, softcap=cfg.attn_softcap,
                        window=window, block_k=bk)


# ---------------------------------------------------------------------------
# paged decode backends (block-pool KV cache, repro.serving)
# ---------------------------------------------------------------------------

@register_backend("xla_paged_decode", decode=True, paged=True,
                  doc="XLA block-table gather + dense masked decode")
def xla_paged_decode(cfg, q, k_pages, v_pages, *, pos_pages, tables, kv_len,
                     pos, window=None, layer=None) -> jax.Array:
    """q: (B, KV, G, Dh); k/v_pages: (L, KV, N, ps, Dh) read at ``layer``,
    or (KV, N, ps, Dh) with ``layer=None``; pos_pages: (N, ps); tables:
    (B, P); kv_len/pos: (B,).  Gathers the sequence's pages of the layer
    into a contiguous (B, KV, P*ps, Dh) view (one gather from the stacked
    pool), then runs the dense decode math with a written-slot mask (slot <
    kv_len) and an original-position window."""
    if layer is None:  # one layer: a stack of one
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    B, KV, G, Dh = q.shape
    ps = k_pages.shape[3]
    P = tables.shape[1]
    S = P * ps
    # [layer, :, tables]: the indexed dims lead -> (B, P, KV, ps, Dh)
    kg = jnp.moveaxis(k_pages[layer, :, tables], 2, 1).reshape(B, KV, S, Dh)
    vg = jnp.moveaxis(v_pages[layer, :, tables], 2, 1).reshape(B, KV, S, Dh)
    pg = pos_pages[tables].reshape(B, S)
    s = jnp.einsum("bkgd,bkld->bkgl", q, kg) * (Dh ** -0.5)
    s = _softcap(s, cfg.attn_softcap)
    slot = jnp.arange(S)[None, :]
    m = slot < kv_len[:, None]
    if window is not None:
        m = m & (pos[:, None] - pg < window)
    s = jnp.where(m[:, None, None, :], s, jnp.asarray(-1e30, s.dtype))
    a = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bkgl,bkld->bkgd", a, vg)


@register_backend("pallas_paged_decode", decode=True, paged=True,
                  doc="Pallas paged decode; block-table gather in the DMA")
def pallas_paged_decode(cfg, q, k_pages, v_pages, *, pos_pages, tables,
                        kv_len, pos, window=None, layer=None) -> jax.Array:
    """Same contract as :func:`xla_paged_decode`, executed by
    ``repro.kernels.paged_decode.paged_flash_decode``."""
    from repro.kernels.paged_decode import paged_flash_decode

    return paged_flash_decode(q, k_pages, v_pages, pos_pages, tables,
                              kv_len, pos, layer=layer,
                              softcap=cfg.attn_softcap, window=window)
