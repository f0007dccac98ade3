import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Drive the full dry-run sweep: every (arch x shape) cell on the single-pod
mesh (roofline baselines) and the multi-pod mesh (the pod-axis proof).

Each cell runs in a fresh subprocess (jax caches device state and compiled
programs; isolation also makes one cell's failure non-fatal) and results
append to a JSON-lines file, so the sweep is resumable.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun_all \
      [--out results/dryrun.jsonl] [--multi-pod] [--only arch:shape ...]
"""

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path


def _done_keys(path: Path):
    done = set()
    if path.exists():
        for line in path.read_text().splitlines():
            try:
                r = json.loads(line)
                done.add((r["arch"], r["shape"], r["mesh"], r.get("spls", False)))
            except Exception:
                pass
    return done


def run_one(arch: str, shape: str, multi_pod: bool, spls: bool,
            timeout: int = 3600):
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape]
    if multi_pod:
        cmd.append("--multi-pod")
    if spls:
        cmd.append("--spls")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    # the pod is virtual CPU devices; a child must never claim the chip
    env["JAX_PLATFORMS"] = "cpu"
    t0 = time.time()
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout, env=env)
        if proc.returncode == 0:
            return json.loads(proc.stdout)
        return {"arch": arch, "shape": shape,
                "mesh": "2x16x16" if multi_pod else "16x16", "spls": spls,
                "error": proc.stderr[-2000:], "wall_s": time.time() - t0}
    except subprocess.TimeoutExpired:
        return {"arch": arch, "shape": shape,
                "mesh": "2x16x16" if multi_pod else "16x16", "spls": spls,
                "error": f"timeout {timeout}s", "wall_s": time.time() - t0}


def main(argv=None):
    from repro.configs.registry import all_cells

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--meshes", default="16x16,2x16x16")
    ap.add_argument("--only", nargs="*", default=None,
                    help="arch:shape filters")
    ap.add_argument("--spls", action="store_true")
    ap.add_argument("--timeout", type=int, default=3600)
    args = ap.parse_args(argv)

    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    done = _done_keys(out)

    cells = list(all_cells(include_skipped=True))
    if args.only:
        want = {tuple(x.split(":")) for x in args.only}
        cells = [c for c in cells if c in want]

    meshes = args.meshes.split(",")
    total = len(cells) * len(meshes)
    i = 0
    for mesh in meshes:
        multi = mesh == "2x16x16"
        for arch, shape in cells:
            i += 1
            key = (arch, shape, mesh, args.spls)
            if key in done:
                continue
            print(f"[{i}/{total}] {arch} x {shape} on {mesh}"
                  f"{' +spls' if args.spls else ''} ...", flush=True)
            res = run_one(arch, shape, multi, args.spls, args.timeout)
            with out.open("a") as f:
                f.write(json.dumps(res, default=str) + "\n")
            status = ("SKIP" if res.get("skipped")
                      else "ERR" if "error" in res else
                      f"ok compile={res.get('compile_s')}s "
                      f"dom={res.get('roofline', {}).get('dominant')}")
            print(f"    -> {status}", flush=True)
    print("sweep complete:", out)


if __name__ == "__main__":
    main()
