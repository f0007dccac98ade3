"""The shared serving entry points: interpret-mode resolution, the
compile-cache placement, ``repro.launch.serve``'s config/engine
construction, and the engine outputs ``chip_smoke.py`` relies on
(first-token logits, the resolved decode backend, unknown backend
names rejected)."""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.kernels.interpret import resolve_interpret
from repro.launch import compile_cache, serve
from repro.serving import Request

ROOT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("given,want", [(None, True), (True, True),
                                        (False, False)])
def test_resolve_interpret_on_cpu(given, want):
    assert jax.default_backend() == "cpu"
    assert resolve_interpret(given) is want


def test_compile_cache_named_from_outside(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.configure_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_serving_config_published_widths():
    cfg = serve.serving_config("qwen3-0.6b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.resolved_head_dim, cfg.d_ff, cfg.vocab_size) == (
        28, 1024, 16, 8, 128, 3072, 151936)
    assert not cfg.remat and not cfg.spls.enabled
    spls = serve.serving_config("qwen3-0.6b", smoke=True, spls=True).spls
    assert spls.enabled and spls.causal
    for k, v in serve.SPLS_SETTINGS.items():
        assert getattr(spls, k) == v, k


def test_launcher_serves_smoke_paged_spls(monkeypatch, tmp_path, capsys):
    # keep the launcher's compile cache out of the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    rc = serve.main(["--arch", "qwen3-0.6b", "--smoke", "--spls",
                     "--requests", "2", "--slots", "2", "--prompt-len", "16",
                     "--max-new", "2", "--page-size", "4",
                     "--prefill-chunk", "8"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["all_done"] and out["retired"] == 2
    assert out["device"]["platform"] == "cpu"
    assert out["pool"]["compute_backend"] == "packed_xla"
    assert out["pool"]["decode_backend"] == "xla_paged_decode"


@pytest.fixture(scope="module")
def smoke():
    cfg = serve.serving_config("qwen3-0.6b", smoke=True)
    return cfg, serve.init_serving_params(cfg, seed=0)


def test_first_logits_are_the_sampled_row(smoke):
    cfg, params = smoke
    eng = serve.build_engine(cfg, params, paged=True, n_slots=2, max_len=32,
                             page_size=4, prefill_chunk=8)
    prompts = [np.arange(12, dtype=np.int32) % cfg.vocab_size,
               np.arange(5, dtype=np.int32) + 7]
    reqs = [Request(rid=0, prompt=prompts[0], max_new_tokens=3,
                    return_logits=True),
            Request(rid=1, prompt=prompts[1], max_new_tokens=3)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained(max_ticks=100)
    assert all(r.done for r in reqs)
    logits = reqs[0].first_logits
    assert logits.shape == (cfg.vocab_size,) and np.all(np.isfinite(logits))
    assert int(np.argmax(logits)) == reqs[0].output[0]      # greedy
    assert reqs[1].first_logits is None
    assert eng.stats["decode_backend"] == "xla_paged_decode"


@pytest.mark.parametrize("name", ["pallas_paged_decod", "xla_densee"])
def test_unknown_backend_name_raises_at_construction(smoke, name):
    cfg, params = smoke
    with pytest.raises(ValueError, match="unknown attention backend"):
        serve.build_engine(cfg, params, paged=True, n_slots=2, max_len=32,
                           page_size=4, attn_backend=name)
