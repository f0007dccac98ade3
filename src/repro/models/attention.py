"""GQA attention with RoPE, qk-norm, sliding window, logit soft-capping,
KV-cache decode, and SPLS sparse execution.

Head layout & tensor parallelism.  Weights keep an explicit (KV, G)
structure (``G = n_heads // n_kv_heads`` query heads per KV group); at trace
time :func:`head_shard_mode` picks how heads bind to the mesh's model axis:

  * **structured** -- KV (or G) divides the model axis: shard that axis
    directly; attention einsums stay local (llama3 kv=8 < 16 shards G=16,
    gemma2/olmoe shard KV=16).
  * **flat** -- neither divides but H = KV*G does (h2o/dbrx/jamba/pixtral:
    kv=8, G<16, H%16==0): flatten heads, repeat the (small, replicated) KV
    heads locally per device -- no communication, each device materializes
    only its H/|model| KV copies.
  * **replicated** -- nothing divides (musicgen H=24): attention replicates
    over the model axis; TP still comes from FFN + vocab.  Noted in
    DESIGN.md.

Execution strategy is delegated to the **attention backend registry**
(:mod:`repro.models.attn_backend`; selection rules documented in
``src/repro/models/README.md``): ``cfg.attn_backend`` (default ``"auto"``)
or an explicit ``backend=`` argument picks between the materialized-scores
path (``xla_dense``), capacity-packed SPLS (``xla_packed``), the KV-chunked
online-softmax scan (``xla_chunked``), and the Pallas flash kernels
(``pallas_flash`` / ``pallas_flash_decode`` -- compiled on TPU, interpret
mode elsewhere), with the SPLS :class:`SparsityPlan` lowered to block-level
K/V skipping + packed critical Q rows on the Pallas path.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.spls import SparsityPlan
from repro.sharding.logical import constrain
from .attn_backend import get_backend, resolve_backend
from .common import apply_rope, dense_init, rms_norm, rope_freqs

__all__ = ["init_attention", "attention_forward", "attention_decode",
           "KVCache", "init_kv_cache", "head_shard_mode", "project_qkv",
           "project_kv", "output_proj", "norm_q", "norm_k"]


class KVCache(NamedTuple):
    k: jax.Array          # (B, KV, S_max, Dh)
    v: jax.Array          # (B, KV, S_max, Dh)


def init_kv_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> KVCache:
    kv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    z = jnp.zeros((batch, kv, max_len, dh), dtype)
    return KVCache(k=z, v=z)


def head_shard_mode(cfg: ArchConfig) -> str:
    """'structured' | 'flat' | 'replicated' -- see module docstring."""
    from repro.sharding.logical import _current_mesh
    mesh = _current_mesh()
    if mesh is None:
        return "structured"
    m = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    KV = cfg.n_kv_heads
    G = cfg.n_heads // max(KV, 1)
    if m <= 1 or KV % m == 0 or G % m == 0:
        return "structured"
    if cfg.n_heads % m == 0:
        return "flat"
    return "padded"


def _pad_heads_to(cfg: ArchConfig) -> int:
    """Padded head count for 'padded' mode: next multiple of |model|.

    Beyond-paper optimization (EXPERIMENTS.md §Perf, musicgen cell): when no
    head factorization divides the model axis (H=24 on 16), the projections
    are zero-padded to H'=32 *at trace time*.  Padded heads produce garbage
    attention outputs but their ``wo`` rows are zero, so the block output is
    bit-identical -- and attention compute/memory shards 16-way instead of
    replicating.
    """
    from repro.sharding.logical import _current_mesh
    mesh = _current_mesh()
    m = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    H = cfg.n_heads
    return -(-H // m) * m


def init_attention(cfg: ArchConfig, key: jax.Array, dtype) -> dict:
    D, KV, Dh = cfg.d_model, cfg.n_kv_heads, cfg.resolved_head_dim
    G = cfg.n_heads // KV
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, KV, G, Dh), dtype, fan_in=D),
        "wk": dense_init(ks[1], (D, KV, Dh), dtype, fan_in=D),
        "wv": dense_init(ks[2], (D, KV, Dh), dtype, fan_in=D),
        "wo": dense_init(ks[3], (KV, G, Dh, D), dtype, fan_in=KV * G * Dh),
    }
    if cfg.qk_norm:
        full = cfg.qk_norm_mode == "full"
        p["q_norm"] = jnp.zeros((cfg.n_heads * Dh,) if full else (Dh,), dtype)
        p["k_norm"] = jnp.zeros((KV * Dh,) if full else (Dh,), dtype)
    return p


def _full_rms_norm(x: jax.Array, gain: jax.Array, eps: float,
                   head_axes: Tuple[int, ...]) -> jax.Array:
    """RMSNorm over a whole projection whose heads are split out on
    ``head_axes`` (ascending, in the projection's order) and the last axis:
    the same numbers as normalising the unsplit ``(..., heads * Dh)``
    projection with the flat gain, as ``rms_norm`` does (offset gain)."""
    dt = x.dtype
    axes = tuple(head_axes) + (x.ndim - 1,)
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=axes, keepdims=True)
    g = gain.astype(jnp.float32).reshape(
        [x.shape[a] if a in axes else 1 for a in range(x.ndim)])
    return (xf * jax.lax.rsqrt(var + eps) * (1.0 + g)).astype(dt)


def norm_q(cfg: ArchConfig, p: dict, q: jax.Array) -> jax.Array:
    """qk-norm of q in the structured layout (B, KV, G, L, Dh)."""
    if cfg.qk_norm_mode == "full":
        return _full_rms_norm(q, p["q_norm"], cfg.norm_eps, (1, 2))
    return rms_norm(q, p["q_norm"], cfg.norm_eps)


def norm_k(cfg: ArchConfig, p: dict, k: jax.Array) -> jax.Array:
    """qk-norm of k in the structured layout (B, KV, L, Dh)."""
    if cfg.qk_norm_mode == "full":
        return _full_rms_norm(k, p["k_norm"], cfg.norm_eps, (1,))
    return rms_norm(k, p["k_norm"], cfg.norm_eps)


def _project_qkv(cfg: ArchConfig, p: dict, x: jax.Array, positions: jax.Array,
                 mode: str = "structured"):
    """x (B, L, D) -> q (B, KV', G', L, Dh), k/v (B, KV', L, Dh).

    structured: KV' = KV, G' = G.   flat: KV' = H, G' = 1 (KV repeated).
    """
    B, L, D = x.shape
    KV, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
    G = cfg.n_heads // KV
    if mode in ("flat", "padded"):
        if cfg.qk_norm and cfg.qk_norm_mode == "full":
            raise NotImplementedError(
                "full-width qk-norm runs in the structured head layout")
        H = KV * G
        wq = p["wq"].reshape(D, H, Dh)
        wk, wv = p["wk"], p["wv"]
        if mode == "padded":
            Hp = _pad_heads_to(cfg)
            wq = jnp.pad(wq, ((0, 0), (0, Hp - H), (0, 0)))
            # pad KV to H' as well (each padded head attends independently)
            wk = jnp.pad(jnp.repeat(wk, G, axis=1),
                         ((0, 0), (0, Hp - H), (0, 0)))
            wv = jnp.pad(jnp.repeat(wv, G, axis=1),
                         ((0, 0), (0, Hp - H), (0, 0)))
            G = 1  # KV now per-head
        q = jnp.einsum("bld,dhe->bhle", x, wq)
        q = constrain(q, ("batch", "heads", "seq", None))
        k = jnp.einsum("bld,dkh->bklh", x, wk)
        v = jnp.einsum("bld,dkh->bklh", x, wv)
        if mode == "flat":
            k = jnp.repeat(k, G, axis=1)
            v = jnp.repeat(v, G, axis=1)
        k = constrain(k, ("batch", "heads", "seq", None))
        v = constrain(v, ("batch", "heads", "seq", None))
        q = q[:, :, None]  # (B, H', 1, L, Dh)
    else:
        q = jnp.einsum("bld,dkgh->bkglh", x, p["wq"])
        k = jnp.einsum("bld,dkh->bklh", x, p["wk"])
        v = jnp.einsum("bld,dkh->bklh", x, p["wv"])
        q = constrain(q, ("batch", "kv_heads", "qgroups", "seq", None))
        k = constrain(k, ("batch", "kv_heads", "seq", None))
        v = constrain(v, ("batch", "kv_heads", "seq", None))
    if cfg.qk_norm:
        q = norm_q(cfg, p, q)
        k = norm_k(cfg, p, k)
    sin, cos = rope_freqs(positions, Dh, cfg.rope_theta)
    q = apply_rope(q, sin[:, None, None], cos[:, None, None])
    k = apply_rope(k, sin[:, None], cos[:, None])
    return q, k, v


def _out_proj(cfg: ArchConfig, p: dict, o: jax.Array, mode: str) -> jax.Array:
    """o (B, KV', G', L, Dh) -> (B, L, D)."""
    KV, Dh, D = cfg.n_kv_heads, cfg.resolved_head_dim, cfg.d_model
    G = cfg.n_heads // KV
    if mode in ("flat", "padded"):
        wo = p["wo"].reshape(KV * G, Dh, D)
        if mode == "padded":
            Hp = _pad_heads_to(cfg)
            # zero wo rows for padded heads -> output bit-identical
            wo = jnp.pad(wo, ((0, Hp - KV * G), (0, 0), (0, 0)))
        out = jnp.einsum("bhld,hdm->blm", o[:, :, 0], wo)
    else:
        out = jnp.einsum("bkgld,kgdm->blm", o, p["wo"])
    return constrain(out, ("batch", "seq", "embed"))


def _project_kv(cfg: ArchConfig, p: dict, x: jax.Array,
                positions: jax.Array, mode: str = "structured",
                perm: Optional[jax.Array] = None,
                compute_backend: str = "dense"):
    """K/V-only projection seam: x (B, L, D) -> k/v (structured layout).

    Row-for-row identical to the k/v half of :func:`_project_qkv`.  The
    seam dispatches on the **compute backend**: with ``perm`` (a packed
    column subset from the horizon-finalized prune vote,
    :mod:`repro.core.planner`) the projection runs packed through
    :func:`repro.sparse_compute.packed.packed_project_kv` -- only the
    surviving ``C = len(perm)`` columns are computed (``(1, KV, C, Dh)``
    out, the ``gathered_matmul`` path) -- while ``perm=None`` keeps the
    dense ``(B, KV, L, Dh)`` projection of every chunk row (required
    until a vote finalizes; ``vote_horizon=None`` serving and all
    non-serving callers).
    """
    assert mode == "structured", "packed serving keeps the structured layout"
    if perm is not None:
        from repro.sparse_compute.packed import packed_project_kv
        assert x.shape[0] == 1, "packed K/V projection is per-sequence"
        return packed_project_kv(cfg, p, x, positions.reshape(-1), perm,
                                 compute_backend)
    Dh = cfg.resolved_head_dim
    k = jnp.einsum("bld,dkh->bklh", x, p["wk"])
    v = jnp.einsum("bld,dkh->bklh", x, p["wv"])
    k = constrain(k, ("batch", "kv_heads", "seq", None))
    v = constrain(v, ("batch", "kv_heads", "seq", None))
    if cfg.qk_norm:
        k = norm_k(cfg, p, k)
    sin, cos = rope_freqs(positions, Dh, cfg.rope_theta)
    k = apply_rope(k, sin[:, None], cos[:, None])
    return k, v


# public seams for alternative execution layers (the paged serving engine
# projects QKV / re-projects outputs itself, around its block-pool cache)
project_qkv = _project_qkv
project_kv = _project_kv
output_proj = _out_proj


def attention_forward(cfg: ArchConfig, p: dict, x: jax.Array,
                      window: Optional[int] = None,
                      plan: Optional[SparsityPlan] = None,
                      q_capacity: Optional[int] = None,
                      kv_capacity: Optional[int] = None,
                      cache_len: Optional[int] = None,
                      backend: Optional[str] = None):
    """Full-sequence attention.  x: (B, L, D) -> (B, L, D).

    With ``cache_len`` set, also returns a right-padded KVCache (prefill);
    the cache always stores the compact (B, KV, S, Dh) layout.  ``backend``
    overrides ``cfg.attn_backend`` (see :mod:`repro.models.attn_backend`).
    """
    B, L, D = x.shape
    KV, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
    G = cfg.n_heads // KV
    mode = head_shard_mode(cfg)
    positions = jnp.broadcast_to(jnp.arange(L), (B, L))
    q, k, v = _project_qkv(cfg, p, x, positions, mode)

    name = resolve_backend(backend or cfg.attn_backend, cfg, L=L, plan=plan,
                           q_capacity=q_capacity)
    o = get_backend(name)(cfg, q, k, v, window=window, plan=plan,
                          q_capacity=q_capacity, kv_capacity=kv_capacity)

    out = _out_proj(cfg, p, o, mode)
    if cache_len is not None:
        kc = k.reshape(B, KV, G, L, Dh)[:, :, 0] if mode == "flat" else k
        vc = v.reshape(B, KV, G, L, Dh)[:, :, 0] if mode == "flat" else v
        pad = [(0, 0), (0, 0), (0, cache_len - L), (0, 0)]
        return out, KVCache(k=jnp.pad(kc, pad), v=jnp.pad(vc, pad))
    return out


def attention_decode(cfg: ArchConfig, p: dict, x: jax.Array, cache: KVCache,
                     pos: jax.Array, window: Optional[int] = None,
                     backend: Optional[str] = None):
    """One-token decode.  x: (B, 1, D); pos: (B,) current write index.

    Returns (out (B, 1, D), new_cache).  The cache is pre-allocated at
    max_len; masking handles both not-yet-written and out-of-window slots.
    Decode keeps the structured layout: the cache stays (B, KV, S, Dh) and
    scores shard over whatever the cache sharding chose (kv heads or seq).
    Dispatches through the decode side of the backend registry
    (``xla_dense_decode`` / ``pallas_flash_decode``).
    """
    B, _, D = x.shape
    KV, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
    G = cfg.n_heads // KV
    q, k_new, v_new = _project_qkv(cfg, p, x, pos[:, None], "structured")

    # per-row scatter of the new KV at `pos` (cheap: no full-cache math)
    upd = jax.vmap(
        lambda c, n, pb: jax.lax.dynamic_update_slice(c, n, (0, pb, 0)))
    k_all = upd(cache.k, k_new, pos)
    v_all = upd(cache.v, v_new, pos)

    name = resolve_backend(backend or cfg.attn_backend, cfg,
                           L=k_all.shape[2], decode=True)
    o = get_backend(name)(cfg, q[:, :, :, 0], k_all, v_all, pos=pos,
                          window=window)
    out = _out_proj(cfg, p, o[:, :, :, None], "structured")
    return out, KVCache(k=k_all, v=v_all)
