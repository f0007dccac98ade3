"""A whole run at a tiny size on the CPU, with the harness's look for a
chip skipped: a sound run comes out correct; the fp8 control and each
fault the serving path can have come out not correct.

The tiny cell is the dense configuration on the program's CPU-sized
variant of Qwen3 (2 layers, d 64, float32 compute) under the
prefill-local mix cut to prompts of 20-100 tokens and 6 served tokens.
At float32 the program serves the reference's own tokens (gap 0), so the
limit here is 1e-3; the full-size limits are in the configuration files.
"""

import json
import os
import time

import pytest

import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIMIT = 1e-3


def tiny_cell(traffic="prefill-local"):
    def load(*p):
        with open(os.path.join(BENCH, *p)) as f:
            return json.load(f)

    conf = load("configs", "qwen3-0.6b-dense.json")
    conf["smoke"] = True
    conf["model"].update({"num_hidden_layers": 2, "hidden_size": 64,
                          "num_attention_heads": 4, "num_key_value_heads": 4,
                          "head_dim": 16, "intermediate_size": 128,
                          "vocab_size": 256})
    conf["precision"]["compute_dtype"] = "float32"
    conf["serve"].update({"n_slots": 4, "max_len": 256, "prefill_chunk": 16})
    conf["check"]["limits"] = {"max_gap": LIMIT}
    mix = load("traffic", traffic + ".json")
    mix["prompt_len"].update({"median": 40, "min": 20, "max": 100})
    mix["output_len"] = {"kind": "fixed", "value": 6}
    bench = load(os.pardir, "BENCHMARK.json")
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    cell = {"name": "tiny", "config": conf["name"], "traffic": traffic,
            "chips": 1}
    return harness.Cell(bench, cell, conf, mix)


def run(fault=None, control=False):
    return harness.run_cell(tiny_cell(), 2 ** 31 + 7, 2.0, False,
                            time.monotonic(), require_tpu=False,
                            control=control, fault=fault)


def test_sound_run_is_correct():
    out = run()
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["max_gap"]["value"] <= LIMIT
    assert set(out["metrics"]) >= {"prompt_tok_s", "setup_s"}
    assert list(out)[-1] == "checks"


def test_control_fails():
    out = run(control=True)
    assert out["correct"] is True
    assert out["checks"]["control"]["correct"] is False
    assert out["checks"]["control"]["max_gap"] > LIMIT


def _token_altered(eng):
    pick = eng._pick
    vocab = eng.cfg.vocab_size
    eng._pick = lambda logits: (pick(logits) + 1) % vocab


def _state_unchanged(eng):
    import jax
    import jax.numpy as jnp

    step = eng._decode

    def stale(p, cache, pos_pages, *rest):
        # the step runs on copies (it donates its inputs) and its new
        # cache is dropped: decode never writes the KV pages
        logits, _, _ = step(p, jax.tree.map(jnp.copy, cache),
                            jnp.copy(pos_pages), *rest)
        return logits, cache, pos_pages

    eng._decode = stale


def _chunk_state_unchanged(eng):
    import jax
    import jax.numpy as jnp

    step = eng._chunk

    def stale(p, cache, pos_pages, *rest):
        # a prefill chunk's KV writes are dropped: later chunks and the
        # first token attend to pages that were never written
        logits, _, _ = step(p, jax.tree.map(jnp.copy, cache),
                            jnp.copy(pos_pages), *rest)
        return logits, cache, pos_pages

    eng._chunk = stale


@pytest.mark.parametrize(
    "fault", [_token_altered, _state_unchanged, _chunk_state_unchanged],
    ids=["token_altered", "state_unchanged", "chunk_state_unchanged"])
def test_fault_is_not_correct(fault):
    out = run(fault=fault)
    assert out["correct"] is False
    assert out["checks"]["max_gap"]["value"] > LIMIT
