"""Kernel microbenchmarks: Pallas (interpret on CPU; compiled on TPU) vs
the pure-jnp oracle, plus max-abs-error per shape; and the attention
backend registry timed dense-vs-pallas-vs-sparse on one workload."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import (flash_attention, flash_decode, hlog_qmatmul,
                           local_similarity_dist)
from repro.kernels import ref
from repro.kernels.interpret import resolve_interpret
from .common import time_call


def _backend_rows():
    """Registry comparison: every forward backend on the same workload,
    dense and under an SPLS plan (timings vs the xla_dense baseline; the
    Pallas rows run in interpret mode on CPU -- numbers are for parity,
    the speed story needs a TPU)."""
    from repro.configs.base import ArchConfig, BlockCfg
    from repro.core.spls import SPLSConfig, SparsityPlan, build_plan
    from repro.models import available_backends, get_backend

    B, H, L, Dh = 1, 4, 256, 64
    D = H * Dh
    cfg = ArchConfig(name="bench", d_model=D, n_heads=H, n_kv_heads=H,
                     head_dim=Dh, causal=True)
    ks = jax.random.split(jax.random.PRNGKey(9), 6)
    q = jax.random.normal(ks[0], (B, H, 1, L, Dh))
    k = jax.random.normal(ks[1], (B, H, L, Dh))
    v = jax.random.normal(ks[2], (B, H, L, Dh))
    plan = build_plan(jax.random.normal(ks[3], (B, L, D)),
                      jax.random.normal(ks[4], (D, D)) * 0.1,
                      jax.random.normal(ks[5], (D, D)) * 0.1,
                      H, SPLSConfig(k_ratio=0.12, s_threshold=0.8,
                                    window=8))
    plan = SparsityPlan(*(t.reshape(B, H, 1, *t.shape[2:])
                          if t.ndim > 2 else t for t in plan))

    rows = []
    interp = resolve_interpret()
    names = sorted(available_backends(decode=False),
                   key=lambda n: n != "xla_dense")  # baseline first
    for with_plan in (False, True):
        pl_ = plan if with_plan else None
        base = None
        for name in names:
            fn = get_backend(name)
            call = jax.jit(lambda q_, k_, v_, fn=fn: fn(
                cfg, q_, k_, v_, plan=pl_, q_capacity=L // 2 if pl_ else None))
            us = time_call(call, q, k, v)
            out = call(q, k, v)
            if base is None:
                base = out
            tag = "spls" if with_plan else "dense"
            rows.append((f"kernel/attn_backend/{name}/{tag}/L{L}", us,
                         {"max_err_vs_xla_dense":
                          round(float(jnp.max(jnp.abs(out - base))), 6),
                          "timing": ("interpret (CPU)"
                                     if interp and "pallas" in name
                                     else "jit")}))
    return rows


def run():
    rows = []
    # hlog matmul
    for M, K, N in ((256, 256, 256), (512, 512, 512)):
        xq = jnp.round(jax.random.normal(jax.random.PRNGKey(0), (M, K)) * 35
                       ).clip(-127, 127)
        wq = jnp.round(jax.random.normal(jax.random.PRNGKey(1), (K, N)) * 35
                       ).clip(-127, 127)
        ref_fn = jax.jit(ref.hlog_qmatmul_ref)
        us_ref = time_call(ref_fn, xq, wq)
        err = float(jnp.max(jnp.abs(
            hlog_qmatmul(xq, wq) - ref_fn(xq, wq))))
        rows.append((f"kernel/hlog_qmatmul/{M}x{K}x{N}", us_ref,
                     {"max_err_vs_oracle": err, "timing": "jnp-oracle (CPU)"}))

    # flash attention
    for L in (256, 512):
        q, k, v = (jax.random.normal(jax.random.PRNGKey(s), (1, 4, L, 64))
                   for s in (2, 3, 4))
        ref_fn = jax.jit(lambda a, b, c: ref.flash_attention_ref(a, b, c))
        us_ref = time_call(ref_fn, q, k, v)
        err = float(jnp.max(jnp.abs(
            flash_attention(q, k, v) - ref_fn(q, k, v))))
        rows.append((f"kernel/flash_attention/L{L}", us_ref,
                     {"max_err_vs_oracle": round(err, 8)}))

    # flash decode (one token vs a 2k cache)
    q = jax.random.normal(jax.random.PRNGKey(6), (2, 2, 4, 64))
    k = jax.random.normal(jax.random.PRNGKey(7), (2, 2, 2048, 64))
    v = jax.random.normal(jax.random.PRNGKey(8), (2, 2, 2048, 64))
    pos = jnp.asarray([2000, 511])
    ref_fn = jax.jit(lambda a, b, c, p: ref.flash_decode_ref(a, b, c, p))
    us_ref = time_call(ref_fn, q, k, v, pos)
    err = float(jnp.max(jnp.abs(
        flash_decode(q, k, v, pos, block_k=512)
        - ref_fn(q, k, v, pos))))
    rows.append(("kernel/flash_decode/S2048", us_ref,
                 {"max_err_vs_oracle": round(err, 8)}))

    # local similarity
    spa = jax.random.normal(jax.random.PRNGKey(5), (2, 4, 64, 512))
    ref_fn = jax.jit(lambda s: ref.local_similarity_ref(s, 8))
    us_ref = time_call(ref_fn, spa)
    err = float(jnp.max(jnp.abs(
        local_similarity_dist(spa, w=8) - ref_fn(spa))))
    rows.append(("kernel/local_similarity/64x512", us_ref,
                 {"max_err_vs_oracle": round(err, 6)}))

    # gathered matmul: double-buffered vs serialized row-DMA gather.
    # Both variants compute the XLA x[perm] @ w oracle; the
    # timed pair isolates what the two-semaphore DMA pipeline buys.  On
    # CPU both run interpret-mode (parity only); on TPU they compile and
    # the timing delta is the measurement ROADMAP carries forward.  The
    # dispatch is wrapped in jax.profiler.TraceAnnotation
    # ("gathered_matmul/{buffered,serialized}"), so a jax.profiler trace
    # of this benchmark names each variant on the TPU timeline.
    from repro.kernels import gathered_matmul

    L, D, F, C = 512, 256, 256, 128
    x = jax.random.normal(jax.random.PRNGKey(10), (L, D))
    w = jax.random.normal(jax.random.PRNGKey(11), (D, F))
    perm = jax.random.randint(jax.random.PRNGKey(12), (C,), 0, L)
    interp = resolve_interpret()
    base = jax.jit(lambda a, b, p: a[p] @ b)(x, w, perm)
    gm_us = {}
    for db in (True, False):
        def call(a, b, p, db=db):
            return gathered_matmul(a, b, p, double_buffer=db)
        us = time_call(call, x, w, perm)
        tag = "buffered" if db else "serialized"
        gm_us[tag] = us
        err = float(jnp.max(jnp.abs(call(x, w, perm) - base)))
        rows.append((f"kernel/gathered_matmul/{tag}/C{C}_D{D}_F{F}", us,
                     {"max_err_vs_oracle": err,
                      "timing": "interpret (CPU)" if interp else "jit"}))
    rows.append(("kernel/gathered_matmul/dma_overlap_summary", 0.0, {
        "us_buffered": round(gm_us["buffered"], 1),
        "us_serialized": round(gm_us["serialized"], 1),
        "overlap_speedup_x": round(
            gm_us["serialized"] / max(gm_us["buffered"], 1e-9), 3),
        "timing": "interpret (CPU)" if interp else "jit"}))

    rows.extend(_backend_rows())
    return rows
