#!/usr/bin/env python3
"""Bring-up check of the paged SPLS serving path on one TPU chip.

Run from the root of a checkout, on a machine with a TPU:

    python3 chip_smoke.py

One process drives the chip through JAX.  Phases, in order; any failure
exits non-zero and prints no result line:

1. device  -- platform, device kind and count, JAX/libtpu versions.  No
   TPU: exit here; nothing ever runs on the CPU.
2. kernels -- every main-path Pallas kernel compiled at Qwen3-0.6B widths
   (its program must hold a Mosaic ``tpu_custom_call``) and checked
   against its ``repro.kernels.ref`` oracle within :data:`KERNEL_TOL`.
3. serve, SPLS + packed -- ``PagedServingEngine`` on ``qwen3-0.6b`` at
   published widths (random weights from a seed) with the serve
   launcher's SPLS settings and ``auto`` backends, which must resolve to
   ``packed_pallas`` and ``pallas_paged_decode``; the same traffic again
   on the XLA twin (``packed_xla`` + ``xla_paged_decode``).
4. serve, dense -- SPLS off, the dense chunk step and
   ``pallas_paged_decode``; the twin runs ``xla_paged_decode``.

In phases 3 and 4 every request must retire in both runs, and each
request's first-token logits must agree with its twin's within
:data:`LOGIT_TOL`.  Greedy-token agreement, peak pages, compile seconds
(set-up) and warm tok/s are printed as information, not as benchmark
results.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

import importlib.metadata
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen3-0.6b"
SEED = 0
# serving shape: 8 requests over 4 slots, prompts of 192..1000 tokens
# built from repeated 16-token motifs (adjacent rows are locally similar,
# so SPLS really packs rows), 16 new tokens each
PROMPT_LENS = (192, 448, 704, 1000, 1000, 704, 448, 192)
MOTIF = 16
MAX_NEW = 16
SERVE = dict(n_slots=4, page_size=16, max_len=1040)
SPLS_CHUNK = 64

# Kernel oracle bounds, as max|out - ref| / max|ref|.  Attention kernels
# write bf16 and may feed the MXU bf16 operands: 2^-8 relative per
# element, so 1e-2 leaves room without admitting a wrong mask or row.
# The gathered matmul accumulates bf16-exact inputs in float32 over at
# most 3072 terms (~3072 * 2^-24 relative); the row gather is a copy.
KERNEL_TOL = {"attention": 1e-2, "matmul": 1e-3, "copy": 0.0}

# First-token logits of a Pallas run vs its XLA twin, as
# ||a - b||_2 / ||b||_2.  Both run the bf16 model; they round at
# different points (the Pallas kernels accumulate and round in float32),
# so 28 layers of bf16 residual updates drift by ~sqrt(28) * 2^-9 ~ 1e-2
# relative (1.3e-2 measured on a v5e).  Dense: 5e-2.  SPLS also makes
# discrete top-k / similarity / capacity decisions on int8-quantized
# predictor inputs that one rounding difference can flip, and each flip
# moves rows between "computed" and "copied from the leader"; the drift
# grows with the number of chunks a prompt streams through.  Even in
# float32 a smoke-width model drifts by up to 7.5e-2 at 256 tokens from
# summation order alone (its dense twin agrees to 1e-6); the bf16 model
# at 1000 tokens drifted by 0.34 on a v5e: 0.5.  The cross-request
# distance printed beside it is what an unrelated logit vector scores.
LOGIT_TOL = {"dense": 5e-2, "spls": 0.5}


def log(msg):
    print(msg, flush=True)


class CompileClock:
    """Sums JAX's own trace/lower/compile durations (persistent-cache
    loads included) -- the set-up share of a cold run."""

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.total += duration


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------

def phase_device():
    import jax
    import jaxlib

    devs = jax.devices()
    d = devs[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "absent"
    log(f"[device] platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)} jax={jax.__version__} jaxlib={jaxlib.__version__}"
        f" libtpu={libtpu}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# phase 2: kernels vs oracles
# ---------------------------------------------------------------------------

def _rel_err(out, ref):
    import numpy as np

    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)), 1e-30))


def kernel_cases(H=16, KV=8, Dh=128, D=1024, FF=3072, L=1024, page=16,
                 slots=4, chunk=64, seed=SEED):
    """(name, kind, fn, args, oracle) at Qwen3-0.6B widths by default."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.flash_decode import flash_decode
    from repro.kernels.gathered_matmul import (gather_rows_kernel,
                                               gathered_matmul)
    from repro.kernels.paged_decode import paged_flash_decode

    G = H // KV
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 32))
    bf = jnp.bfloat16

    def rnd(shape, dtype=bf):
        return jax.random.normal(next(ks), shape, jnp.float32).astype(dtype)

    def f32(a):
        return a.astype(jnp.float32)

    def rep(a):  # grouped KV heads -> per-query-head, for the oracles
        return jnp.repeat(f32(a), G, axis=1)

    cases = []
    q, k, v = rnd((1, H, L, Dh)), rnd((1, KV, L, Dh)), rnd((1, KV, L, Dh))
    cases.append(("flash_attention", "attention",
                  lambda q, k, v: flash_attention(q, k, v, causal=True),
                  (q, k, v),
                  lambda: ref.flash_attention_ref(f32(q), rep(k), rep(v),
                                                  causal=True)))

    # SPLS lowering: half the rows packed (original ids in q_pos), a
    # random column keep mask with one whole dead block
    perm = jnp.sort(jax.random.permutation(next(ks), L)[:L // 2])
    keep = jax.random.bernoulli(next(ks), 0.7, (1, H, L))
    keep = keep.at[:, :, 0].set(True).at[:, :, L // 4:L * 3 // 8].set(False)
    q_pos = jnp.broadcast_to(perm.astype(jnp.int32), (1, H, L // 2))
    qp = q[:, :, perm]
    cases.append(("flash_attention+q_pos+kv_keep", "attention",
                  lambda q, k, v, p, m: flash_attention(
                      q, k, v, causal=True, q_pos=p, kv_keep=m),
                  (qp, k, v, q_pos, keep),
                  lambda: ref.flash_attention_ref(
                      f32(q), rep(k), rep(v), causal=True,
                      kv_keep=keep)[:, :, perm]))

    # paged decode over a shuffled page pool; rows at several fill levels
    P = L // page
    n_pages = slots * P + 1
    kp, vp = rnd((KV, n_pages, page, Dh)), rnd((KV, n_pages, page, Dh))
    pos_pages = jnp.arange(n_pages * page, dtype=jnp.int32).reshape(
        n_pages, page)
    tables = (1 + jax.random.permutation(next(ks), slots * P)
              ).reshape(slots, P).astype(jnp.int32)
    kv_len = jnp.asarray([L, L // 2 + 5, chunk, 1][:slots], jnp.int32)
    pos = kv_len - 1
    qd = rnd((slots, KV, G, Dh))
    cases.append(("paged_flash_decode", "attention",
                  lambda *a: paged_flash_decode(*a),
                  (qd, kp, vp, pos_pages, tables, kv_len, pos),
                  lambda: ref.paged_decode_ref(f32(qd), f32(kp), f32(vp),
                                               pos_pages, tables, kv_len,
                                               pos)))

    kc, vc = rnd((slots, KV, L, Dh)), rnd((slots, KV, L, Dh))
    cases.append(("flash_decode", "attention",
                  lambda *a: flash_decode(*a), (qd, kc, vc, pos),
                  lambda: ref.flash_decode_ref(f32(qd), f32(kc), f32(vc),
                                               pos)))

    # packed linear ops of one prefill chunk: Q projection and the FFN
    # up-projection with the fused leader scatter
    x = rnd((chunk, D))
    perm_c = jax.random.randint(next(ks), (chunk * 3 // 4,), 0, chunk)
    slot = jax.random.randint(next(ks), (chunk,), 0, chunk * 3 // 4)
    wq, wup = rnd((D, H * Dh)), rnd((D, FF))
    cases.append(("gathered_matmul", "matmul",
                  lambda x, w, p: gathered_matmul(x, w, p), (x, wq, perm_c),
                  lambda: ref.gathered_matmul_ref(x, wq, perm_c)))
    cases.append(("gathered_matmul+src_slot", "matmul",
                  lambda x, w, p, s: gathered_matmul(x, w, p, src_slot=s),
                  (x, wup, perm_c, slot),
                  lambda: ref.gathered_matmul_ref(x, wup, perm_c,
                                                  src_slot=slot)))
    src = rnd((chunk * 3 // 4, D), jnp.float32)
    cases.append(("gather_rows_kernel", "copy",
                  lambda s, i: gather_rows_kernel(s, i), (src, slot),
                  lambda: np.asarray(src)[np.asarray(slot)]))
    return cases


def phase_kernels(cases):
    import jax

    failed = []
    for name, kind, fn, args, oracle in cases:
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        t_compile = time.perf_counter() - t0
        mosaic = "tpu_custom_call" in compiled.as_text()
        out = jax.block_until_ready(compiled(*args))
        with jax.default_matmul_precision("highest"):
            want = oracle()
        err = _rel_err(out, want)
        ok = err <= KERNEL_TOL[kind] and mosaic
        log(f"[kernels] {name}: rel_err={err:.3e} (tol {KERNEL_TOL[kind]:g})"
            f" tpu_custom_call={mosaic} compile_s={t_compile:.2f}"
            f" {'ok' if ok else 'FAIL'}")
        if not ok:
            failed.append(name)
    if failed:
        raise AssertionError(f"kernels failed: {failed}")


# ---------------------------------------------------------------------------
# phases 3 and 4: serving
# ---------------------------------------------------------------------------

def make_prompts(vocab, lens=PROMPT_LENS, motif=MOTIF, seed=SEED):
    """Prompts whose tokens repeat in runs of ``motif`` (each run's token
    drawn from the seed), as ``examples/serve_batch.py --prompt-repeat``
    builds them."""
    import numpy as np

    rng = np.random.default_rng(seed)
    return [np.repeat(rng.integers(0, vocab, n // motif + 1), motif)[:n]
            .astype(np.int32) for n in lens]


def serve_once(eng, prompts, rid0, max_new=MAX_NEW):
    """Submit ``prompts`` and drain; returns (requests, wall_s)."""
    import jax.numpy as jnp

    from repro.serving import Request

    reqs = [Request(rid=rid0 + i, prompt=jnp.asarray(p),
                    max_new_tokens=max_new, return_logits=True)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run_until_drained(max_ticks=100000)
    return reqs, time.perf_counter() - t0


def serve_run(label, cfg, params, prompts, clock, device, warm=True,
              **serve_kw):
    """One engine over the traffic: the first pass (cold, compiles
    included) is the one compared; with ``warm`` a second pass times the
    warm engine."""
    from repro.launch.serve import build_engine

    eng = build_engine(cfg, params, paged=True, **serve_kw)
    c0 = clock.total
    reqs, wall = serve_once(eng, prompts, rid0=0)
    compile_s = clock.total - c0
    stats = eng.stats
    log(f"[{label}] compute_backend={stats['compute_backend']} "
        f"decode_backend={stats['decode_backend']} "
        f"prefill_chunks={stats['prefill_chunks']} "
        f"peak_pages={stats['peak_pages']} "
        f"flops_saved_pct={ {k: round(v, 1) for k, v in stats['flops_saved_pct'].items()} }")
    log(f"[{label}] on {device}: cold pass {wall:.1f}s of which compile "
        f"{compile_s:.1f}s (set-up) -- information, not a benchmark result")
    retired = all(r.done for r in reqs)
    if warm:
        c1 = clock.total
        again, warm_wall = serve_once(eng, prompts, rid0=len(prompts))
        toks = sum(len(r.output) for r in again)
        log(f"[{label}] on {device}: warm pass {toks / warm_wall:.1f} tok/s "
            f"({toks} tokens in {warm_wall:.2f}s, compile "
            f"{clock.total - c1:.1f}s) -- information, not a benchmark "
            f"result")
        retired = retired and all(r.done for r in again)
    return {"reqs": reqs, "stats": stats, "retired": retired}


def compare(label, run, twin, tol):
    import numpy as np

    if not (run["retired"] and twin["retired"]):
        raise AssertionError(f"{label}: not every request retired")
    def rel_l2(x, y):
        return float(np.linalg.norm(x - y) / max(np.linalg.norm(y), 1e-30))

    rels, agree, total = [], 0, 0
    for a, b in zip(run["reqs"], twin["reqs"]):
        la, lb = a.first_logits, b.first_logits
        if la is None or lb is None or not np.all(np.isfinite(la)):
            raise AssertionError(f"{label}: request {a.rid} has no finite "
                                 f"first-token logits")
        rels.append(rel_l2(la, lb))
        n = min(len(a.output), len(b.output))
        agree += sum(x == y for x, y in zip(a.output[:n], b.output[:n]))
        total += max(len(a.output), len(b.output))
    worst = max(rels)
    own = [r.first_logits for r in run["reqs"]]
    cross = min(rel_l2(own[i], own[j]) for i in range(len(own))
                for j in range(len(own)) if i != j)
    log(f"[{label}] first-token logits vs twin: worst rel_l2={worst:.3e} "
        f"(tol {tol:g}); per request "
        f"{' '.join(f'{r:.3e}' for r in rels)}")
    log(f"[{label}] information: closest pair of different requests "
        f"rel_l2={cross:.3e}; greedy-token agreement {agree}/{total}")
    if worst > tol:
        raise AssertionError(f"{label}: logits differ from the twin by "
                             f"{worst:.3e} > {tol}")


def phase_spls(params, prompts, clock, device, cfg):
    serve = dict(SERVE, prefill_chunk=SPLS_CHUNK)
    run = serve_run("spls", cfg, params, prompts, clock, device,
                    compute_backend="auto", attn_backend="auto", **serve)
    if (run["stats"]["compute_backend"] != "packed_pallas"
            or run["stats"]["decode_backend"] != "pallas_paged_decode"):
        raise AssertionError(f"spls: auto resolved to "
                             f"{run['stats']['compute_backend']} / "
                             f"{run['stats']['decode_backend']}")
    twin = serve_run("spls-twin", cfg, params, prompts, clock, device,
                     warm=False, compute_backend="packed_xla",
                     attn_backend="xla_paged_decode", **serve)
    compare("spls", run, twin, LOGIT_TOL["spls"])


def phase_dense(params, prompts, clock, device, cfg):
    serve = dict(SERVE, prefill_chunk=SPLS_CHUNK)
    run = serve_run("dense", cfg, params, prompts, clock, device,
                    attn_backend="auto", **serve)
    if run["stats"]["decode_backend"] != "pallas_paged_decode":
        raise AssertionError(f"dense: auto resolved to "
                             f"{run['stats']['decode_backend']}")
    twin = serve_run("dense-twin", cfg, params, prompts, clock, device,
                     warm=False, attn_backend="xla_paged_decode", **serve)
    compare("dense", run, twin, LOGIT_TOL["dense"])


# ---------------------------------------------------------------------------

def main():
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke: run from a checkout (src/repro not found next to "
              "this script)", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import configure_compile_cache

    log(f"[setup] compile cache: {configure_compile_cache()}")
    import jax

    from repro.launch.serve import init_serving_params, serving_config
    from repro.models import attn_backend

    t_start = time.perf_counter()
    clock = CompileClock()
    device = phase_device()
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU found; refusing to run on "
              f"{device['platform']}", file=sys.stderr)
        return 1
    # a mistyped or wrong-kind backend raises instead of falling back
    attn_backend.STRICT_BACKEND_KIND = True
    dev = f"{device['platform']}:{device['kind']}x{device['count']}"

    failed = []

    def run_phase(name, fn, *args):
        t0 = time.perf_counter()
        try:
            fn(*args)
            log(f"[{name}] passed in {time.perf_counter() - t0:.1f}s")
        except Exception:
            traceback.print_exc()
            log(f"[{name}] FAILED after {time.perf_counter() - t0:.1f}s")
            failed.append(name)

    run_phase("kernels", lambda: phase_kernels(kernel_cases()))
    cfg = serving_config(ARCH)
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_serving_params(cfg, SEED))
    log(f"[setup] {cfg.name}: {cfg.param_count() / 1e6:.0f}M parameters "
        f"initialized from seed {SEED} in {time.perf_counter() - t0:.1f}s")
    prompts = make_prompts(cfg.vocab_size)
    run_phase("spls", phase_spls, params, prompts, clock, dev,
              serving_config(ARCH, spls=True))
    run_phase("dense", phase_dense, params, prompts, clock, dev, cfg)
    log(f"[setup] total {time.perf_counter() - t_start:.1f}s, of which "
        f"compile {clock.total:.1f}s")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
