"""The serving tests' plain reference: one request at a time through the
model's own whole-prompt ``prefill`` and cached ``decode_step``.

The paged engine must reproduce these greedy tokens bit for bit: under
SPLS the prefill builds the progressive (streaming-reproducible) plan,
which is what chunked prefill streams one chunk at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import decode_step, prefill


@functools.lru_cache(maxsize=None)
def _programs(cfg, max_len: int):
    plan_mode = "progressive" if cfg.spls.enabled else "auto"
    return (jax.jit(functools.partial(prefill, cfg, max_len=max_len,
                                      plan_mode=plan_mode)),
            jax.jit(functools.partial(decode_step, cfg)))


def greedy_reference(cfg, params, prompt, max_new: int,
                     max_len: int) -> list:
    """Greedy tokens for ``prompt``: ``max_new`` of them, or fewer where
    the sequence reaches ``max_len - 1`` positions first, as the engine
    stops."""
    pre, dec = _programs(cfg, max_len)
    tokens = jnp.asarray(prompt, jnp.int32)[None, :]
    logits, cache = pre(params, tokens)
    out = [int(jnp.argmax(logits[0, -1]))]
    pos = tokens.shape[1]
    while len(out) < max_new and pos < max_len - 1:
        logits, cache = dec(params, cache,
                            jnp.asarray([[out[-1]]], jnp.int32),
                            jnp.asarray([pos], jnp.int32))
        out.append(int(jnp.argmax(logits[0, 0])))
        pos += 1
    return out


def greedy_references(cfg, params, reqs, max_len: int) -> list:
    """``greedy_reference`` of each request's prompt and token budget."""
    return [greedy_reference(cfg, params, r.prompt, r.max_new_tokens,
                             max_len) for r in reqs]
