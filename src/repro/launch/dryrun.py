import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and record memory / FLOPs / collective-traffic analysis.

The three lines above MUST run before any jax import (jax locks the device
count on first init); they give this process 512 placeholder CPU devices so
``jax.make_mesh`` can build the 16x16 single-pod and 2x16x16 multi-pod
meshes, and pin it to the CPU so it never claims an attached accelerator
(the pod exists only as virtual CPU devices).  Nothing is allocated:
inputs are ShapeDtypeStructs and the step is only lowered and compiled.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-405b \
      --shape train_4k [--multi-pod] [--spls] [--out results.json]
"""

import argparse
import json
import sys
import time

import jax

from repro.configs.registry import get_config, get_shape
from repro.launch.hlo_analysis import parse_hlo_stats
from repro.launch.mesh import make_production_mesh, mesh_axis_sizes
from repro.launch.specs import input_specs
from repro.launch.steps import (make_prefill_step, make_serve_step,
                                make_train_step)
from repro.optim import AdamWConfig, adamw_init
from repro.optim.schedules import warmup_cosine
from repro.sharding.logical import axis_rules
from repro.sharding.rules import activation_rules, opt_state_sharding

# TPU v5e hardware constants for the roofline terms
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             spls: bool = False, n_micro: int = None,
             donate: bool = True) -> dict:
    import dataclasses

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    if shape_name not in cfg.supported_shapes:
        return {"arch": arch, "shape": shape_name,
                "mesh": f"{'2x' if multi_pod else ''}16x16", "spls": spls,
                "skipped": True,
                "reason": "unsupported shape (see DESIGN.md)"}
    if spls and cfg.has_attn:
        from repro.core.spls import SPLSConfig
        cfg = dataclasses.replace(cfg, spls=SPLSConfig(
            enabled=True, k_ratio=0.12, s_threshold=0.6, f_threshold=6,
            window=8, causal=cfg.causal,
            q_capacity_ratio=0.5, kv_capacity_ratio=0.75))

    mesh = make_production_mesh(multi_pod=multi_pod)
    sizes = mesh_axis_sizes(mesh)
    n_chips = 1
    for v in sizes.values():
        n_chips *= v

    specs = input_specs(cfg, shape, mesh)
    t0 = time.time()
    with axis_rules(activation_rules(mesh), mesh):
        if specs["kind"] == "train":
            mb = n_micro or (cfg.microbatch or {}).get(shape_name, 1)
            data_par = n_chips // sizes.get("model", 1)
            per_shard = max(shape.global_batch // data_par, 1)
            n_acc = max(per_shard // mb, 1)
            step = make_train_step(
                cfg, AdamWConfig(moment_dtype=None),
                warmup_cosine(3e-4, 100, 10000), n_micro=n_acc)
            opt_abs = jax.eval_shape(
                lambda p: adamw_init(AdamWConfig(), p), specs["params"])
            oshard = opt_state_sharding(specs["param_sharding"], opt_abs)
            opt_abs = jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=s),
                opt_abs, oshard)
            fn = jax.jit(step, donate_argnums=(0, 1) if donate else ())
            lowered = fn.lower(specs["params"], opt_abs, specs["batch"])
        elif specs["kind"] == "prefill":
            step = make_prefill_step(cfg)
            lowered = jax.jit(step).lower(specs["params"], specs["inputs"])
        else:
            step = make_serve_step(cfg)
            fn = jax.jit(step, donate_argnums=(1,) if donate else ())
            lowered = fn.lower(specs["params"], specs["cache"],
                               specs["tokens"], specs["pos"])
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    stats = parse_hlo_stats(compiled.as_text())

    # All parsed numbers are PER DEVICE (the HLO is the SPMD program), with
    # while-loop trip counts applied -- XLA's own cost_analysis() counts
    # scanned layer bodies once, so we parse the HLO ourselves (see
    # hlo_analysis.py) and keep the raw numbers for reference.
    flops_dev = stats["dot_flops"]
    bytes_dev = stats["traffic_bytes"]
    coll_dev = stats["collective_bytes"]

    model_flops = _model_flops(cfg, shape)
    result = {
        "arch": arch, "shape": shape_name, "kind": specs["kind"],
        "mesh": f"{'2x' if multi_pod else ''}16x16", "chips": n_chips,
        "spls": spls, "skipped": False,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes_per_device": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes_per_device": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes_per_device": getattr(mem, "alias_size_in_bytes", None),
        },
        "hlo_flops_per_device": flops_dev,
        "hlo_bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_dev,
        "collective_breakdown": {k[5:]: v for k, v in stats.items()
                                 if k.startswith("coll:")},
        "xla_cost_analysis_raw": {"flops": float(cost.get("flops", 0.0)),
                                  "bytes": float(cost.get("bytes accessed", 0.0))},
        "model_flops_total": model_flops,
        "roofline": {
            "compute_s": flops_dev / PEAK_FLOPS,
            "memory_s": bytes_dev / HBM_BW,
            "collective_s": coll_dev / ICI_BW,
        },
    }
    terms = result["roofline"]
    dom = max(terms, key=terms.get)
    result["roofline"]["dominant"] = dom
    total_hlo_flops = flops_dev * n_chips
    result["model_flops_ratio"] = (model_flops / total_hlo_flops
                                   if total_hlo_flops else None)
    return result


def _model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6 N D (dense) / 6 N_active D for MoE; decode: D=B tokens."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # decode: one token per row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--spls", action="store_true",
                    help="enable the paper's SPLS sparsity in the step")
    ap.add_argument("--n-micro", type=int, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    res = run_cell(args.arch, args.shape, args.multi_pod, args.spls,
                   args.n_micro)
    js = json.dumps(res, indent=2, default=str)
    print(js)
    if args.out:
        with open(args.out, "w") as f:
            f.write(js)
    return 0 if (res.get("skipped") or res.get("compile_s") is not None) else 1


if __name__ == "__main__":
    sys.exit(main())
