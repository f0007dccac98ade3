"""The benchmark's one door into the program under test (``src/repro``).

Everything else under ``bench/`` is the yardstick and imports nothing of
the program.  From the program the benchmark takes the serving engine it
drives, the engine's telemetry spans and counters, and nothing else: the
weights, the traffic and the reference are the benchmark's own.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class ProgramMissing(RuntimeError):
    pass


def import_program():
    """Put the checkout's ``src`` on the path; fail where it is absent."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise ProgramMissing(f"no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def configure_compile_cache() -> str:
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``), caching every program however fast it
    compiled, so that a later run of the cell compiles nothing."""
    import jax

    from repro.launch.compile_cache import configure_compile_cache as cc

    where = cc()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


def serving_config(conf: dict):
    """The program's ArchConfig for a configuration file, checked against
    the file's published sizes and stated settings."""
    from repro.launch.serve import SPLS_SETTINGS, serving_config as sc

    spls = conf["spls"]
    cfg = sc(conf["arch"], smoke=conf.get("smoke", False),
             spls=spls["enabled"])
    m = conf["model"]
    got = {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
           "num_attention_heads": cfg.n_heads,
           "num_key_value_heads": cfg.n_kv_heads,
           "head_dim": cfg.resolved_head_dim,
           "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
           "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
           "tie_word_embeddings": cfg.tied_embeddings,
           "hidden_act": cfg.ffn_activation}
    want = {k: m[k] for k in got}
    if got != want:
        raise ValueError(f"{conf['name']}: the program's {conf['arch']} is "
                         f"{got}, the configuration states {want}")
    prec = conf["precision"]
    if (cfg.param_dtype, cfg.compute_dtype) != (prec["param_dtype"],
                                                prec["compute_dtype"]):
        raise ValueError(f"{conf['name']}: the program stores "
                         f"{cfg.param_dtype} and computes {cfg.compute_dtype}")
    if spls["enabled"]:
        stated = {k: spls[k] for k in SPLS_SETTINGS}
        if stated != SPLS_SETTINGS:
            raise ValueError(f"{conf['name']}: SPLS {stated} differs from "
                             f"the program's {SPLS_SETTINGS}")
    return cfg


def check_layout(cfg, weights) -> None:
    """The benchmark's weights must match the program's parameter tree."""
    import jax

    from repro.models import abstract_params

    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                        abstract_params(cfg))
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), weights)
    if jax.tree.structure(want) != jax.tree.structure(got) or \
            jax.tree.leaves(want) != jax.tree.leaves(got):
        raise ValueError("the benchmark's weights do not match the "
                         "program's parameter layout")


def build_engine(cfg, weights, serve: dict):
    from repro.launch.serve import build_engine as be

    return be(cfg, weights, paged=True, **serve)


def request(rid: int, prompt, max_new: int):
    from repro.serving import Request

    return Request(rid=rid, prompt=prompt, max_new_tokens=max_new)


def spans(eng):
    """The engine's telemetry events (Chrome B/E/i dicts, monotonic
    seconds)."""
    return list(eng.telemetry.trace.events)


def aborted(eng) -> int:
    return int(eng.stats["aborted"])
