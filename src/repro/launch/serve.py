"""Serving launcher: ``python -m repro.launch.serve --arch <id>``.

Builds the paged serving engine (chunked prefill, admission keyed on free
pages, SPLS page pruning) at the architecture's published widths, with
random weights drawn from ``--seed``, and drives a synthetic request
stream through it.  ``--spls`` turns on the paper's sparsity with
:data:`SPLS_SETTINGS`.  ``--smoke`` swaps in the architecture's CPU-sized
variant (``ArchConfig.smoke()``) for runs without an accelerator.  The
engine serves causal token models with attention-only periods (SSM state
is O(1) per slot and is not paged).

``serving_config`` and ``build_engine`` are the construction path every
serving entry point shares (``chip_smoke.py`` included).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time

import jax

__all__ = ["SPLS_SETTINGS", "serving_config", "init_serving_params",
           "build_engine", "main"]

# the SPLS operating point of the serving launcher (paper defaults scaled
# to serving chunks: window 4 divides every prefill_chunk it is run with)
SPLS_SETTINGS = dict(k_ratio=0.25, s_threshold=0.6, f_threshold=2, window=4)


def serving_config(arch: str, *, smoke: bool = False, spls: bool = False):
    """The ArchConfig a serving run uses: published widths (or the smoke
    variant), no rematerialization, and SPLS at :data:`SPLS_SETTINGS`."""
    from repro.configs.registry import get_config
    from repro.core.spls import SPLSConfig

    cfg = get_config(arch)
    if smoke:
        cfg = cfg.smoke()
    cfg = dataclasses.replace(cfg, remat=False)
    if spls and cfg.has_attn:
        cfg = dataclasses.replace(cfg, spls=SPLSConfig(
            enabled=True, causal=cfg.causal, **SPLS_SETTINGS))
    return cfg


def init_serving_params(cfg, seed: int = 0):
    """Random weights from ``seed``, initialized in one compiled program."""
    from repro.models import init_params

    return jax.jit(functools.partial(init_params, cfg))(
        jax.random.PRNGKey(seed))


def build_engine(cfg, params, *, paged: bool = True, **serve_kw):
    """A ``PagedServingEngine`` over ``cfg``/``params``; ``serve_kw`` are
    ``ServeConfig`` fields.  ``paged`` must be True: there is no other
    engine."""
    from repro.serving import PagedServingEngine, ServeConfig

    if not paged:
        raise ValueError("build_engine(paged=False): the dense serving "
                         "engine is gone; every engine is paged")
    return PagedServingEngine(cfg, params, ServeConfig(**serve_kw))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="CPU-sized variant of the architecture")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--spls", action="store_true")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=64)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import configure_compile_cache
    from repro.serving import Request

    configure_compile_cache()
    cfg = serving_config(args.arch, smoke=args.smoke, spls=args.spls)
    if cfg.input_mode != "tokens" or cfg.has_mamba or not cfg.causal:
        print(f"{cfg.name}: not servable by this engine -- skipping")
        return 0
    params = init_serving_params(cfg, args.seed)
    # SPLS prefill packs rows where the platform has a packed kernel
    eng = build_engine(cfg, params, n_slots=args.slots,
                       max_len=args.prompt_len + args.max_new + 8,
                       page_size=args.page_size,
                       prefill_chunk=args.prefill_chunk,
                       compute_backend="auto")
    reqs = []
    for i in range(args.requests):
        prompt = jax.random.randint(jax.random.PRNGKey(args.seed + i),
                                    (args.prompt_len,), 0, cfg.vocab_size)
        r = Request(rid=i, prompt=prompt, max_new_tokens=args.max_new)
        reqs.append(r)
        eng.submit(r)
    t0 = time.perf_counter()
    done = eng.run_until_drained(max_ticks=100000)
    wall = time.perf_counter() - t0
    dev = jax.devices()[0]
    tokens = sum(len(r.output) for r in reqs)
    out = {"arch": cfg.name, "requests": len(reqs), "retired": len(done),
           "all_done": all(r.done for r in reqs),
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices())},
           # includes compilation: a first run's rate is mostly set-up
           "tok_per_s_incl_compile": tokens / wall,
           "outputs": {r.rid: r.output[:8] for r in reqs[:4]},
           "pool": {k: eng.stats[k] for k in
                    ("peak_pages", "preemptions", "prefill_chunks",
                     "compute_backend", "decode_backend")}}
    print(json.dumps(out, indent=1))
    return 0 if out["all_done"] else 1


if __name__ == "__main__":
    sys.exit(main())
