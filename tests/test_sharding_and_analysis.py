"""Sharding rules (logical axes, divisibility fallback, param specs) and
the trip-count-corrected HLO analyzer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.hlo_analysis import parse_hlo_collectives, parse_hlo_stats
from repro.launch.mesh import make_cpu_mesh
from repro.sharding.logical import axis_rules, constrain, logical_to_mesh
from repro.sharding.rules import (activation_rules, batch_sharding,
                                  param_sharding)

jax.config.update("jax_platform_name", "cpu")


class TestLogicalRules:
    def test_no_rules_is_identity_spec(self):
        spec = logical_to_mesh(["batch", "embed"], rules=None)
        assert spec == P(None, None)

    def test_basic_binding(self):
        rules = {"batch": "data", "ffn": "model"}
        spec = logical_to_mesh(["batch", None, "ffn"], rules=rules)
        assert spec == P("data", None, "model")

    def test_divisibility_fallback(self):
        mesh = make_cpu_mesh(1, 1)
        rules = {"kv": "model"}
        # dim 7 not divisible by model size -> replicated... model size is
        # 1 here so use an artificial rules check via shape gate
        spec = logical_to_mesh(["kv"], shape=[7], rules=rules, mesh=mesh)
        assert spec == P("model")  # size-1 axis always divides

    def test_duplicate_mesh_axis_dedup(self):
        rules = {"heads": "model", "ffn": "model"}
        spec = logical_to_mesh(["heads", "ffn"], rules=rules)
        assert spec == P("model", None)  # first binding wins

    def test_constrain_noop_outside_context(self):
        x = jnp.ones((4, 4))
        y = constrain(x, ("batch", "embed"))
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_constrain_inside_context(self):
        mesh = make_cpu_mesh(1, 1)
        with axis_rules(activation_rules(mesh), mesh):
            x = jnp.ones((4, 4))
            y = jax.jit(lambda a: constrain(a, ("batch", None)))(x)
            np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


class TestParamSharding:
    def test_specs_cover_all_leaves(self):
        from repro.configs.registry import get_config
        from repro.models.model import abstract_params
        cfg = get_config("qwen3-0.6b").smoke()
        mesh = make_cpu_mesh(1, 1)
        ab = abstract_params(cfg)
        shd = param_sharding(cfg, mesh, ab)
        n_ab = len(jax.tree.leaves(ab))
        n_sh = len(jax.tree.leaves(
            shd, is_leaf=lambda x: isinstance(x, NamedSharding)))
        assert n_ab == n_sh
        for s in jax.tree.leaves(
                shd, is_leaf=lambda x: isinstance(x, NamedSharding)):
            assert isinstance(s, NamedSharding)

    def test_batch_sharding_fallback(self):
        mesh = make_cpu_mesh(1, 1)
        assert batch_sharding(mesh, 8).spec == P(("data",))
        # batch=1 on data=1 divides; simulate non-divisible via prime
        assert batch_sharding(mesh, 7).spec == P(("data",))


_HLO_SAMPLE = """
HloModule test

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}

%body.1 (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %w = f32[8,8]{1,0} constant({...})
  %d = f32[8,8]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8]{1,0} all-reduce(%d), replica_groups={}, to_apply=%add
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%ni, %ar)
}

%cond.1 (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(5)
  ROOT %c = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (x: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %t0 = (s32[], f32[8,8]) tuple(%zero, %x)
  %w = (s32[], f32[8,8]) while(%t0), condition=%cond.1, body=%body.1
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""


class TestHLOAnalysis:
    def test_while_trip_count_multiplies(self):
        stats = parse_hlo_stats(_HLO_SAMPLE)
        # dot: 2 * 64 * 8 flops, x5 trips
        assert stats["dot_flops"] == 2 * 64 * 8 * 5
        # all-reduce result 8*8*4 bytes x5
        assert stats["coll:all-reduce"] == 8 * 8 * 4 * 5

    def test_collectives_wrapper(self):
        out = parse_hlo_collectives(_HLO_SAMPLE)
        assert out["all-reduce"] == 1280
        assert out["total"] == 1280

    def test_backend_config_trip_count_preferred(self):
        hlo = _HLO_SAMPLE.replace(
            "condition=%cond.1, body=%body.1",
            'condition=%cond.1, body=%body.1, '
            'backend_config={"known_trip_count":{"n":"7"}}')
        stats = parse_hlo_stats(hlo)
        assert stats["dot_flops"] == 2 * 64 * 8 * 7

    def test_real_compiled_program(self):
        """Analyzer vs XLA cost_analysis on an unscanned jit program."""
        def f(x, w):
            return jax.nn.relu(x @ w) @ w.T

        x = jnp.ones((32, 64))
        w = jnp.ones((64, 128))
        compiled = jax.jit(f).lower(x, w).compile()
        stats = parse_hlo_stats(compiled.as_text())
        ca = compiled.cost_analysis()
        # dots dominate; analyzer within 10% of XLA flops
        assert abs(stats["dot_flops"] - ca["flops"]) / ca["flops"] < 0.1

    def test_scanned_program_scales_with_trips(self):
        def f(x):
            w = jnp.ones((16, 16))

            def body(c, _):
                return jnp.tanh(c @ w), None

            out, _ = jax.lax.scan(body, x, None, length=10)
            return out

        compiled = jax.jit(f).lower(jnp.ones((4, 16))).compile()
        stats = parse_hlo_stats(compiled.as_text())
        assert stats["dot_flops"] == pytest.approx(2 * 4 * 16 * 16 * 10,
                                                   rel=0.01)
