"""Integration tests: checkpoint/restart, failure healing, elastic
re-meshing, straggler detection, gradient compression, data determinism,
the serving engine, and the optimizer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings
from _propcheck import strategies as st

from repro.checkpoint import (cleanup_old, latest_step, restore_checkpoint,
                              save_checkpoint)
from repro.configs.base import ArchConfig, BlockCfg
from repro.data.pipeline import DataConfig, synthetic_batch
from repro.optim import (AdamWConfig, adamw_init, adamw_update, compress,
                         decompress, global_norm)
from repro.runtime import (FailureSimulator, Heartbeat, StragglerDetector,
                           Trainer, TrainerConfig, plan_elastic_mesh,
                           rescale_batch)
from repro.runtime.fault_tolerance import retry_with_backoff

jax.config.update("jax_platform_name", "cpu")


def _tiny_cfg(**kw):
    base = dict(name="tiny", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                head_dim=16, d_ff=64, vocab_size=64,
                period=(BlockCfg(),), remat=False)
    base.update(kw)
    return ArchConfig(**base)


def _tiny_data(cfg):
    return DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(12).reshape(3, 4).astype(jnp.float32),
                "b": (jnp.ones((2,)), {"c": jnp.zeros((5,), jnp.int32)})}
        save_checkpoint(str(tmp_path), 7, tree, data_step=7)
        like = jax.tree.map(jnp.zeros_like, tree)
        got, step, dstep = restore_checkpoint(str(tmp_path), like)
        assert step == 7 and dstep == 7
        jax.tree.map(lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)), got, tree)

    def test_latest_and_cleanup(self, tmp_path):
        tree = {"x": jnp.ones(3)}
        for s in (10, 20, 30, 40):
            save_checkpoint(str(tmp_path), s, tree, keep=2)
        assert latest_step(str(tmp_path)) == 40
        steps = sorted(int(d.name[5:]) for d in tmp_path.iterdir()
                       if d.name.startswith("step_"))
        assert steps == [30, 40]

    def test_uncommitted_ignored(self, tmp_path):
        tree = {"x": jnp.ones(3)}
        save_checkpoint(str(tmp_path), 5, tree)
        # fake a partial write
        d = tmp_path / "step_000000099"
        (d / "arrays").mkdir(parents=True)
        (d / "MANIFEST.json").write_text("{}")
        assert latest_step(str(tmp_path)) == 5

    def test_restore_casts_dtype(self, tmp_path):
        save_checkpoint(str(tmp_path), 1, {"x": jnp.ones(3, jnp.float32)})
        got, _, _ = restore_checkpoint(str(tmp_path),
                                       {"x": jnp.zeros(3, jnp.bfloat16)})
        assert got["x"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# trainer: end-to-end + healing
# ---------------------------------------------------------------------------

class TestTrainer:
    def test_loss_decreases(self):
        cfg = _tiny_cfg()
        t = Trainer(cfg, TrainerConfig(total_steps=60, log_every=10),
                    _tiny_data(cfg))
        out = t.run()
        losses = [m["loss"] for m in out["metrics"]]
        assert out["final_step"] == 60
        assert losses[-1] < losses[0] - 0.3, losses

    def test_restart_resumes_from_checkpoint(self, tmp_path):
        cfg = _tiny_cfg()
        tc = TrainerConfig(total_steps=30, ckpt_dir=str(tmp_path),
                           ckpt_every=10, log_every=5)
        t1 = Trainer(cfg, tc, _tiny_data(cfg))
        t1.restore_or_init()
        while t1.step < 20:
            b = synthetic_batch(t1.data_cfg, t1.step)
            t1.params, t1.opt_state, _ = t1._train_step(
                t1.params, t1.opt_state, b)
            t1.step += 1
            if t1.step % 10 == 0:
                t1.save()
        # fresh trainer resumes at 20, not 0
        t2 = Trainer(cfg, tc, _tiny_data(cfg))
        t2.restore_or_init()
        assert t2.step == 20

    def test_heals_injected_failures(self, tmp_path):
        cfg = _tiny_cfg()
        sim = FailureSimulator(fail_at_steps=(12, 23))
        t = Trainer(cfg, TrainerConfig(total_steps=30,
                                       ckpt_dir=str(tmp_path),
                                       ckpt_every=5, log_every=10),
                    _tiny_data(cfg), failure_sim=sim)
        out = t.run()
        assert out["final_step"] == 30  # survived two failures

    def test_spls_trains(self):
        from repro.core.spls import SPLSConfig
        cfg = _tiny_cfg(spls=SPLSConfig(enabled=True, k_ratio=0.3,
                                        s_threshold=0.6, f_threshold=1,
                                        window=4))
        t = Trainer(cfg, TrainerConfig(total_steps=30, log_every=10),
                    _tiny_data(cfg))
        out = t.run()
        assert np.isfinite(out["metrics"][-1]["loss"])


# ---------------------------------------------------------------------------
# fault tolerance primitives
# ---------------------------------------------------------------------------

class TestFaultTolerance:
    def test_heartbeat(self):
        now = [0.0]
        hb = Heartbeat(timeout_s=10.0, clock=lambda: now[0])
        hb.ping("a")
        hb.ping("b")
        now[0] = 5.0
        hb.ping("a")
        now[0] = 12.0
        assert hb.dead_hosts() == ["b"]
        assert hb.alive_hosts() == ["a"]

    def test_straggler_detection(self):
        sd = StragglerDetector(threshold=2.0)
        for host in ("a", "b", "c"):
            for _ in range(8):
                sd.record(host, 1.0)
        sd.record("c", 5.0)
        assert sd.stragglers() == ["c"]

    def test_retry_with_backoff(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        assert retry_with_backoff(flaky, max_retries=5,
                                  sleep=lambda s: None) == "ok"
        assert len(calls) == 3

    def test_retry_exhausts(self):
        with pytest.raises(OSError):
            retry_with_backoff(lambda: (_ for _ in ()).throw(OSError("x")),
                               max_retries=2, sleep=lambda s: None)


class TestElastic:
    def test_plan_survives_node_loss(self):
        plan = plan_elastic_mesh(alive=[f"h{i}" for i in range(60)],
                                 chips_per_host=4, model_parallel=16)
        assert plan.model == 16
        assert plan.data == 8  # 240 chips -> 15 data -> pow2 8

    def test_plan_raises_when_too_small(self):
        with pytest.raises(RuntimeError):
            plan_elastic_mesh(alive=["h0"], chips_per_host=4,
                              model_parallel=16)

    def test_rescale_policies(self):
        assert rescale_batch(256, 16, 8, "keep_global") == 256
        assert rescale_batch(256, 16, 8, "keep_per_shard") == 128

    def test_reshard_roundtrip_across_meshes(self):
        """A checkpoint written under one sharding restores onto another
        mesh -- the elastic-restart path."""
        import tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_cpu_mesh
        x = jnp.arange(64.0).reshape(8, 8)
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, 1, {"x": x})
            mesh = make_cpu_mesh(1, 1)
            shd = {"x": NamedSharding(mesh, P("data", None))}
            got, _, _ = restore_checkpoint(d, {"x": jnp.zeros_like(x)},
                                           shardings=shd)
            np.testing.assert_array_equal(np.asarray(got["x"]), np.asarray(x))


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

class TestGradCompression:
    def test_roundtrip_error_bounded(self):
        g = jax.random.normal(jax.random.PRNGKey(0), (1000,))
        q, scale, res = compress(g)
        deq = decompress(q, scale, g.shape)
        # block-quantized int8: error <= scale/2 per element
        err = np.abs(np.asarray(deq - g))
        assert err.max() <= float(scale.max()) * 0.51 + 1e-7

    def test_error_feedback_accumulates(self):
        """Residual re-injection: mean of dequantized grads over many steps
        converges to the true mean (error feedback kills the bias)."""
        g = jax.random.normal(jax.random.PRNGKey(1), (512,)) * 1e-3
        res = jnp.zeros_like(g)
        acc = jnp.zeros_like(g)
        for _ in range(64):
            q, scale, res = compress(g, res)
            acc = acc + decompress(q, scale, g.shape)
        np.testing.assert_allclose(np.asarray(acc / 64), np.asarray(g),
                                   atol=2e-5)

    def test_compression_ratio(self):
        g = jax.random.normal(jax.random.PRNGKey(2), (4096,))
        q, scale, _ = compress(g)
        raw = g.size * 4
        packed = q.size * 1 + scale.size * 4
        assert packed < raw / 3.5  # ~4x minus per-block scales

    def test_int8_codes_in_range(self):
        g = jax.random.normal(jax.random.PRNGKey(3), (300,)) * 100
        q, _, _ = compress(g)
        assert q.dtype == jnp.int8
        assert int(jnp.max(jnp.abs(q.astype(jnp.int32)))) <= 127


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

class TestData:
    def test_deterministic_restart(self):
        cfg = DataConfig(seed=3, seq_len=16, global_batch=2)
        a = synthetic_batch(cfg, 41)
        b = synthetic_batch(cfg, 41)
        np.testing.assert_array_equal(np.asarray(a["inputs"]),
                                      np.asarray(b["inputs"]))

    def test_steps_differ(self):
        cfg = DataConfig(seed=3, seq_len=16, global_batch=2)
        a = synthetic_batch(cfg, 1)
        b = synthetic_batch(cfg, 2)
        assert not np.array_equal(np.asarray(a["inputs"]),
                                  np.asarray(b["inputs"]))

    def test_lm_task_is_learnable_structure(self):
        cfg = DataConfig(seed=0, seq_len=256, global_batch=4, ngram=2)
        batch = synthetic_batch(cfg, 0)
        # tokens are in range and not constant
        toks = np.asarray(batch["inputs"])
        assert toks.min() >= 0 and toks.max() < cfg.vocab_size
        assert len(np.unique(toks)) > 10

    def test_embeddings_mode(self):
        cfg = DataConfig(seed=0, seq_len=16, global_batch=2,
                         input_mode="embeddings", d_model=32)
        b = synthetic_batch(cfg, 0)
        assert b["inputs"].shape == (2, 15, 32)
        assert b["labels"].shape == (2, 15)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

class TestAdamW:
    def test_converges_on_quadratic(self):
        p = {"w": jnp.asarray([5.0, -3.0])}
        cfg = AdamWConfig(weight_decay=0.0, clip_norm=None)
        st_ = adamw_init(cfg, p)
        for _ in range(300):
            g = jax.tree.map(lambda w: 2 * w, p)
            p, st_, _ = adamw_update(cfg, g, st_, p, jnp.asarray(0.05))
        assert float(jnp.abs(p["w"]).max()) < 0.05

    def test_weight_decay_shrinks(self):
        p = {"w": jnp.ones(4)}
        cfg = AdamWConfig(weight_decay=0.5, clip_norm=None)
        st_ = adamw_init(cfg, p)
        g = {"w": jnp.zeros(4)}
        p2, _, _ = adamw_update(cfg, g, st_, p, jnp.asarray(0.1))
        assert float(p2["w"][0]) < 1.0

    def test_clip_bounds_update(self):
        p = {"w": jnp.zeros(3)}
        cfg = AdamWConfig(clip_norm=1.0, weight_decay=0.0)
        st_ = adamw_init(cfg, p)
        g = {"w": jnp.full((3,), 1e6)}
        _, _, m = adamw_update(cfg, g, st_, p, jnp.asarray(0.1))
        assert float(m["grad_norm"]) > 1e5  # reported pre-clip

    @given(st.floats(1e-5, 1e-1), st.integers(0, 5))
    @settings(max_examples=10, deadline=None)
    def test_update_finite(self, lr, seed):
        p = {"w": jax.random.normal(jax.random.PRNGKey(seed), (8,))}
        cfg = AdamWConfig()
        st_ = adamw_init(cfg, p)
        g = {"w": jax.random.normal(jax.random.PRNGKey(seed + 1), (8,))}
        p2, _, _ = adamw_update(cfg, g, st_, p, jnp.asarray(lr))
        assert np.isfinite(np.asarray(p2["w"])).all()


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------

class TestServing:
    @staticmethod
    def _forward_greedy(cfg, params, prompt, n):
        """Greedy tokens by repeated whole-sequence ``forward``."""
        from repro.models import forward
        seq = list(np.asarray(prompt))
        for _ in range(n):
            lg = forward(cfg, params, jnp.asarray(seq)[None, :])
            seq.append(int(jnp.argmax(lg[0, -1])))
        return seq[len(prompt):]

    def test_engine_matches_sequential_decode(self):
        from repro.models import init_params
        from repro.serving import PagedServingEngine, Request, ServeConfig
        cfg = _tiny_cfg()
        params = init_params(cfg, jax.random.PRNGKey(0))
        prompt = jax.random.randint(jax.random.PRNGKey(1), (12,), 0,
                                    cfg.vocab_size)
        want = self._forward_greedy(cfg, params, prompt, 6)

        eng = PagedServingEngine(cfg, params, ServeConfig(
            n_slots=2, max_len=32, page_size=4))
        req = Request(rid=0, prompt=prompt, max_new_tokens=6)
        eng.submit(req)
        ticks = 0
        while not req.done and ticks < 50:
            eng.tick()
            ticks += 1
        assert req.output == want

    def test_reference_matches_sequential_decode(self):
        """The serving tests' reference (whole-prompt prefill, then cached
        decode) gives repeated ``forward``'s greedy tokens."""
        from _reference import greedy_reference
        from repro.models import init_params
        cfg = _tiny_cfg()
        params = init_params(cfg, jax.random.PRNGKey(0))
        prompt = jax.random.randint(jax.random.PRNGKey(1), (12,), 0,
                                    cfg.vocab_size)
        assert greedy_reference(cfg, params, prompt, 6, max_len=32) == \
            self._forward_greedy(cfg, params, prompt, 6)

    def test_continuous_batching_drains_queue(self):
        from repro.models import init_params
        from repro.serving import PagedServingEngine, Request, ServeConfig
        cfg = _tiny_cfg()
        params = init_params(cfg, jax.random.PRNGKey(0))
        eng = PagedServingEngine(cfg, params, ServeConfig(
            n_slots=2, max_len=48, page_size=4))
        reqs = []
        for i in range(5):  # more requests than slots
            prompt = jax.random.randint(jax.random.PRNGKey(i), (8,), 0,
                                        cfg.vocab_size)
            r = Request(rid=i, prompt=prompt, max_new_tokens=4)
            reqs.append(r)
            eng.submit(r)
        ticks = 0
        while not eng.sched.idle() and ticks < 200:
            eng.tick()
            ticks += 1
        assert all(r.done for r in reqs)
        assert all(len(r.output) == 4 for r in reqs)
