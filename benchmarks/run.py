"""Benchmark driver -- one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and persists every module's rows
as a ``BENCH_<module>.json`` artifact at the repo root (schema: one
``{"benchmark", "schema_version", "rows": [{name, us_per_call,
derived}]}`` object per module), so each PR leaves a machine-readable
perf trajectory next to the prose claims (ROADMAP item 5).  The
``throughput`` module additionally writes ``BENCH_serving.json`` -- the
telemetry-derived serving report (see
:mod:`repro.observability.report`).  Usage:
  PYTHONPATH=src python -m benchmarks.run [--only reduction ...]
  [--no-artifacts]
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback
from pathlib import Path

MODULES = {
    "reduction": "Fig 15  computation reduction breakdown",
    "quantization": "Figs 7/17/18 + Table III  HLog vs PoT vs APoT",
    "thresholds": "Figs 16/19  s/window/f sweeps",
    "throughput": "Fig 20 + Table IV  cycle/energy model",
    "kernels": "Pallas kernel validation + timing",
    "accuracy": "Sec V-B  accuracy-vs-sparsity proxy",
    "roofline": "Dry-run roofline table (reads results/dryrun.jsonl)",
}

REPO_ROOT = Path(__file__).resolve().parents[1]

ARTIFACT_SCHEMA_VERSION = 1


def write_artifact(name: str, rows) -> Path:
    """Persist one module's rows as BENCH_<name>.json at the repo root."""
    path = REPO_ROOT / f"BENCH_{name}.json"
    payload = {
        "benchmark": name,
        "schema_version": ARTIFACT_SCHEMA_VERSION,
        "rows": [{"name": rn, "us_per_call": us, "derived": d}
                 for rn, us, d in rows],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True, default=str)
        f.write("\n")
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help=f"subset of {sorted(MODULES)}")
    ap.add_argument("--no-artifacts", action="store_true",
                    help="print CSV only; skip BENCH_*.json files")
    args = ap.parse_args(argv)
    names = args.only or list(MODULES)

    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        try:
            mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
            rows = list(mod.run())
            for row_name, us, derived in rows:
                print(f"{row_name},{us:.1f},"
                      f"\"{json.dumps(derived, default=str)}\"")
                sys.stdout.flush()
            if not args.no_artifacts:
                path = write_artifact(name, rows)
                print(f"# wrote {path}", file=sys.stderr)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name}/FAILED,0,\"{traceback.format_exc(limit=3)!r}\"")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
