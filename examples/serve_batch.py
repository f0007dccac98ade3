"""Serve a small model with batched requests through the continuous-
batching paged engine (chunked prefill, admission on free pages, SPLS page
pruning).

  PYTHONPATH=src python examples/serve_batch.py [--spls]
"""

import argparse
import dataclasses
import time

import jax

from repro.configs.base import ArchConfig, BlockCfg
from repro.core.spls import SPLSConfig
from repro.models import init_params
from repro.serving import PagedServingEngine, Request, ServeConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--spls", action="store_true")
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--compute-backend", default=None,
                    choices=["dense", "packed_xla", "packed_pallas", "auto"],
                    help="end-to-end sparse compute on the SPLS chunked "
                         "prefill path (repro.sparse_compute)")
    ap.add_argument("--s-threshold", type=float, default=0.6,
                    help="SPLS similarity threshold (higher -> more rows "
                         "similar -> more packed-compute savings)")
    ap.add_argument("--vote-horizon", type=int, default=None,
                    help="finalize the SPLS column prune vote after this "
                         "many chunks instead of end-of-prefill "
                         "(core.planner; 1 packs the K/V projection)")
    ap.add_argument("--prune-vote", type=float, default=0.5,
                    help="cross-head agreement fraction a column must win "
                         "to keep its page slot (and, under a finite "
                         "--vote-horizon, to keep its K/V projection)")
    ap.add_argument("--k-ratio", type=float, default=0.25,
                    help="SPLS row-wise top-k ratio (smaller -> sparser "
                         "column votes -> more K/V pruning)")
    ap.add_argument("--capacity-margin", type=float, default=1.25,
                    help="capacity-controller safety margin over the EMA "
                         "estimate (1.0 = tightest buckets)")
    ap.add_argument("--prompt-repeat", type=int, default=None,
                    metavar="N",
                    help="make prompts repetitive: token i of every "
                         "prompt is drawn from an N-token motif pool "
                         "resampled every N positions (adjacent rows "
                         "become locally similar, so the SPLS packed "
                         "path actually sparsifies -- random prompts "
                         "barely do)")
    ap.add_argument("--no-telemetry", action="store_true",
                    help="disable the serving telemetry (no-op sinks; "
                         "back-compat stats counters keep working)")
    ap.add_argument("--bench-json", default=None, metavar="PATH",
                    help="write the telemetry-derived BENCH_serving.json "
                         "report to PATH (requires telemetry)")
    ap.add_argument("--trace-json", default=None, metavar="PATH",
                    help="write the Chrome trace (open in "
                         "https://ui.perfetto.dev) to PATH")
    args = ap.parse_args()
    if args.bench_json and args.no_telemetry:
        ap.error("--bench-json needs telemetry (drop --no-telemetry)")

    cfg = ArchConfig(
        name="serve-demo", n_layers=4, d_model=128, n_heads=8, n_kv_heads=4,
        head_dim=16, d_ff=512, vocab_size=512,
        period=(BlockCfg(mixer="attn"),), remat=False,
        spls=SPLSConfig(enabled=args.spls, k_ratio=args.k_ratio,
                        s_threshold=args.s_threshold,
                        f_threshold=3, window=8, causal=True))
    params = init_params(cfg, jax.random.PRNGKey(0))
    scfg = ServeConfig(n_slots=args.slots,
                       max_len=args.prompt_len + args.max_new + 8,
                       page_size=args.page_size,
                       prefill_chunk=args.prefill_chunk,
                       compute_backend=args.compute_backend,
                       vote_horizon=args.vote_horizon,
                       spls_prune_vote=args.prune_vote,
                       capacity_margin=args.capacity_margin,
                       telemetry=not args.no_telemetry)
    eng = PagedServingEngine(cfg, params, scfg)

    reqs = []
    for i in range(args.requests):
        if args.prompt_repeat:
            import numpy as np
            n = args.prompt_repeat
            motifs = np.asarray(jax.random.randint(
                jax.random.PRNGKey(100 + i),
                (args.prompt_len // n + 1,), 0, cfg.vocab_size))
            prompt = jax.numpy.asarray(
                np.repeat(motifs, n)[:args.prompt_len], jax.numpy.int32)
        else:
            prompt = jax.random.randint(jax.random.PRNGKey(100 + i),
                                        (args.prompt_len,), 0,
                                        cfg.vocab_size)
        r = Request(rid=i, prompt=prompt, max_new_tokens=args.max_new)
        reqs.append(r)
        eng.submit(r)

    t0 = time.perf_counter()
    done = eng.run_until_drained(max_ticks=2000)
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.output) for r in reqs)
    dev = jax.devices()[0]
    print(f"requests={len(reqs)} slots={args.slots} spls={args.spls} "
          f"retired={len(done)}")
    print(f"decoded {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens/dt:.1f} tok/s on {dev.platform}:"
          f"{dev.device_kind}, compile included)")
    print(f"pool: peak_pages={eng.stats['peak_pages']} "
          f"preemptions={eng.stats['preemptions']} "
          f"prefill_chunks={eng.stats['prefill_chunks']}")
    fs = eng.stats["flops_saved_pct"]
    print(f"compute: backend={eng.stats['compute_backend']} "
          f"flops_saved qkv={fs['qkv']:.1f}% attn={fs['attn']:.1f}% "
          f"ffn={fs['ffn']:.1f}% kv={fs.get('kv', 0.0):.1f}%")
    assert all(r.done for r in reqs), "queue did not drain"
    assert len(done) == len(reqs)
    if args.bench_json:
        from repro.observability import serving_report, write_report

        report = serving_report(eng, wall_s=dt, extra={
            "workload": {"requests": args.requests,
                         "prompt_len": args.prompt_len,
                         "max_new": args.max_new,
                         "prompt_repeat": args.prompt_repeat}})
        write_report(args.bench_json, report)
        lat = report["latency"]
        print(f"wrote {args.bench_json} "
              f"(ttft_p50={lat['ttft_ms']['p50']:.1f}ms "
              f"tpot_p50={lat['tpot_ms']['p50']:.2f}ms)")
    if args.trace_json:
        eng.telemetry.trace.validate()
        eng.telemetry.trace.write(args.trace_json)
        print(f"wrote {args.trace_json} "
              f"({len(eng.telemetry.trace.events)} events; open in "
              f"https://ui.perfetto.dev)")
    for r in reqs[:3]:
        print(f"  req {r.rid}: {r.output}")


if __name__ == "__main__":
    main()
