"""The generic causal LM / encoder: scan-over-periods composition.

The layer stack is ``cfg.period`` (a tuple of heterogeneous blocks) repeated
``cfg.n_periods`` times.  Period parameters are stacked on a leading axis and
the stack is traversed with ``lax.scan`` so the lowered HLO contains *one*
period body regardless of depth -- essential to keep the 40-cell multi-pod
dry-run compile times sane (llama3-405b has 126 layers).  ``cfg.remat``
wraps the period body in ``jax.checkpoint`` for training.

Modality frontends (audio/vlm archs) are STUBS per the assignment: with
``cfg.input_mode == "embeddings"`` the model consumes precomputed frame /
patch embeddings of shape (B, L, D) instead of token ids.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.sharding.logical import constrain
from .blocks import (block_decode, block_forward, init_block,
                     init_block_cache)
from .common import cast_compute, dense_init, dtype_of, rms_norm, softcap

__all__ = ["init_params", "abstract_params", "forward", "loss_fn",
           "init_cache", "decode_step", "prefill", "embed_inputs",
           "head_logits"]

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_period(cfg: ArchConfig, key: jax.Array, dtype) -> Tuple[dict, ...]:
    ks = jax.random.split(key, len(cfg.period))
    return tuple(init_block(cfg, blk, k, dtype)
                 for blk, k in zip(cfg.period, ks))


def init_params(cfg: ArchConfig, key: jax.Array) -> Params:
    dtype = dtype_of(cfg.param_dtype)
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    p: Params = {}
    p["embed"] = dense_init(k_embed, (cfg.vocab_size, cfg.d_model), dtype,
                            fan_in=cfg.d_model)
    pkeys = jax.random.split(k_layers, cfg.n_periods)
    p["periods"] = jax.vmap(lambda k: _init_period(cfg, k, dtype))(pkeys)
    p["final_norm"] = jnp.zeros((cfg.d_model,), dtype)
    if not cfg.tied_embeddings:
        p["lm_head"] = dense_init(k_head, (cfg.d_model, cfg.vocab_size),
                                  dtype, fan_in=cfg.d_model)
    return p


def abstract_params(cfg: ArchConfig) -> Params:
    """ShapeDtypeStruct pytree -- no allocation (dry-run path)."""
    return jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

def _embed_in(cfg: ArchConfig, params: Params, inputs: jax.Array) -> jax.Array:
    dtype = dtype_of(cfg.compute_dtype)
    if cfg.input_mode == "tokens":
        x = params["embed"][inputs].astype(dtype)
    else:  # modality stub: precomputed embeddings
        x = inputs.astype(dtype)
    if cfg.scale_embedding:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)
    return constrain(x, ("batch", "seq", "embed"))


def _head_out(cfg: ArchConfig, params: Params, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    w = (params["embed"].T if cfg.tied_embeddings else params["lm_head"])
    logits = jnp.einsum("...d,dv->...v", x, w.astype(x.dtype))
    logits = softcap(logits, cfg.final_softcap)
    return constrain(logits, ("batch", "seq", "vocab"))


# public seams for alternative execution layers (e.g. the paged serving
# engine in repro.serving, which runs its own period scan over a paged cache)
def embed_inputs(cfg: ArchConfig, params: Params, inputs: jax.Array):
    """Token/embedding frontend: (B, L)[int] or (B, L, D) -> (B, L, D)."""
    return _embed_in(cfg, params, inputs)


def head_logits(cfg: ArchConfig, params: Params, x: jax.Array) -> jax.Array:
    """Final norm + LM head: (B, L, D) -> (B, L, V)."""
    return _head_out(cfg, params, x)


def _period_fn(cfg: ArchConfig, x: jax.Array, pparams) -> jax.Array:
    dtype = dtype_of(cfg.compute_dtype)
    pparams = cast_compute(pparams, dtype)
    # layer-boundary activations (the remat save points) are seq-sharded
    # over the model axis (Megatron sequence parallelism)
    x = constrain(x, ("batch", "act_seq", "embed"))
    for blk, bp in zip(cfg.period, pparams):
        x = block_forward(cfg, blk, bp, x)
    return x


def forward(cfg: ArchConfig, params: Params, inputs: jax.Array) -> jax.Array:
    """inputs: (B, L) int tokens or (B, L, D) embeddings -> (B, L, V)."""
    x = _embed_in(cfg, params, inputs)
    body = functools.partial(_period_fn, cfg)
    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)

    def scan_body(carry, pparams):
        return body(carry, pparams), None

    x, _ = jax.lax.scan(scan_body, x, params["periods"])
    return _head_out(cfg, params, x)


def loss_fn(cfg: ArchConfig, params: Params, batch: Dict[str, jax.Array]
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Cross-entropy LM loss.  batch: {inputs, labels[, mask]}."""
    logits = forward(cfg, params, batch["inputs"])
    labels = batch["labels"]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = nll.sum() / denom
    acc = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
    metrics = {"loss": loss, "accuracy": (acc * mask).sum() / denom,
               "tokens": mask.sum()}
    return loss, metrics


# ---------------------------------------------------------------------------
# serving: prefill + single-token decode over a scanned cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int):
    """Stacked-over-periods cache pytree (ShapeDtypeStruct-compatible)."""
    dtype = dtype_of(cfg.compute_dtype)

    def one(_):
        return tuple(init_block_cache(cfg, blk, batch, max_len, dtype)
                     for blk in cfg.period)

    return jax.vmap(one)(jnp.arange(cfg.n_periods))


def decode_step(cfg: ArchConfig, params: Params, cache, tokens: jax.Array,
                pos: jax.Array):
    """One decode step.  tokens: (B, 1) or (B, 1, D); pos: (B,).

    Returns (logits (B, 1, V), new_cache).  The period scan threads the
    token activation as carry and the per-period cache as scanned xs/ys.
    """
    x = _embed_in(cfg, params, tokens)
    dtype = dtype_of(cfg.compute_dtype)

    def scan_body(x, inp):
        pparams, pcache = inp
        pparams = cast_compute(pparams, dtype)
        new_caches = []
        for blk, bp, bc in zip(cfg.period, pparams, pcache):
            x, nc = block_decode(cfg, blk, bp, x, bc, pos)
            new_caches.append(nc)
        return x, tuple(new_caches)

    x, new_cache = jax.lax.scan(scan_body, x, (params["periods"], cache))
    return _head_out(cfg, params, x), new_cache


def prefill(cfg: ArchConfig, params: Params, inputs: jax.Array,
            max_len: Optional[int] = None, plan_mode: str = "auto"):
    """Process a full prompt, returning (logits, cache) for decoding.

    When SPLS is enabled this is exactly the paper's scenario: the sparsity
    plan is predicted per block before QKV generation and the prompt is
    processed sparsely; the KV cache still holds every position (pruned
    columns would be an additional paper-faithful saving -- see DESIGN.md).
    ``plan_mode="progressive"`` selects the streaming-reproducible plan
    builder (see :func:`repro.models.blocks.block_forward`): the serving
    engine's chunked prefill reproduces it exactly.
    """
    L = inputs.shape[1]
    S = max_len or L
    dtype = dtype_of(cfg.compute_dtype)
    x = _embed_in(cfg, params, inputs)

    def scan_body(x, pparams):
        pparams = cast_compute(pparams, dtype)
        caches = []
        for blk, bp in zip(cfg.period, pparams):
            x, c = block_forward(cfg, blk, bp, x, cache_len=S,
                                 plan_mode=plan_mode)
            caches.append(c)
        return x, tuple(caches)

    x, cache = jax.lax.scan(scan_body, x, params["periods"])
    return _head_out(cfg, params, x), cache
