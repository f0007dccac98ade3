"""Jit'd public wrappers over the Pallas kernels.

On an accelerator these dispatch the compiled kernels; on a CPU host they
run the kernel bodies in interpret mode (the kernels' ``interpret=None``
default, :func:`repro.kernels.interpret.resolve_interpret`), which is
bit-accurate but slow -- the tests validate against the pure-jnp oracles in
``ref.py`` either way.  ``use_pallas=False`` falls straight through to the
reference implementation (the default inside the model code, where XLA's own
fusion is already near-roofline for dense shapes; the kernels matter on TPU
for the SPLS-sparse and SWA paths).
"""

from __future__ import annotations

from typing import Optional

import jax

from . import ref
from .flash_attention import flash_attention
from .hlog_qmatmul import hlog_qmatmul
from .local_similarity import local_similarity_dist

__all__ = ["predict_matmul", "attention", "window_distances",
           "flash_attention", "hlog_qmatmul", "local_similarity_dist"]


def predict_matmul(xq: jax.Array, wq: jax.Array,
                   use_pallas: bool = True) -> jax.Array:
    """Fused HLog-project + matmul (PAM prediction hot spot)."""
    M, K = xq.shape
    N = wq.shape[1]
    tileable = M % 128 == 0 and N % 128 == 0 and K % 128 == 0
    if use_pallas and tileable:
        return hlog_qmatmul(xq, wq)
    return ref.hlog_qmatmul_ref(xq, wq)


def attention(q, k, v, causal: bool = True, window: Optional[int] = None,
              softcap: Optional[float] = None,
              kv_keep: Optional[jax.Array] = None,
              use_pallas: bool = True) -> jax.Array:
    """Flash attention with SWA / softcap / SPLS column mask."""
    L, Lk = q.shape[2], k.shape[2]
    tileable = L % 128 == 0 and Lk % 128 == 0
    if use_pallas and tileable:
        return flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, kv_keep=kv_keep)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   softcap=softcap, kv_keep=kv_keep)


def window_distances(spa: jax.Array, w: int = 8,
                     use_pallas: bool = True) -> jax.Array:
    """Windowed pairwise L1 distances (similarity-unit hot spot)."""
    L, Lk = spa.shape[2], spa.shape[3]
    if use_pallas and L % w == 0 and Lk % 128 == 0:
        return local_similarity_dist(spa, w=w)
    return ref.local_similarity_ref(spa, w)
