"""Engine tracing on the profiler's clock: the host-phase spans that
``PagedServingEngine.tick()`` opens (``Telemetry.span``), the names of the
engine's jitted programs, the named stages inside the compiled steps, and
the page counts on ``decode_tick``."""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import ArchConfig, BlockCfg
from repro.core.spls import SPLSConfig
from repro.kernels.paged_decode import pages_visited
from repro.models import init_params
from repro.models.common import cast_compute
from repro.observability import PHASE_PID, Telemetry
from repro.serving import PagedServingEngine, Request, ServeConfig
from repro.serving.engine import sample_tokens
from repro.serving.paged_model import STAGES

jax.config.update("jax_platform_name", "cpu")

PHASES = {"engine/admit", "engine/prefill_chunk", "engine/emit_first",
          "engine/retire", "engine/decode_prepare",
          "engine/decode_dispatch", "engine/decode_readback",
          "engine/pool_observe"}
_PARAMS = {}


def _cfg(spls=False, **kw):
    if spls:
        kw.update(name="tiny-spls-tr", spls=SPLSConfig(
            enabled=True, k_ratio=0.12, s_threshold=0.6, f_threshold=2,
            window=4, causal=True))
    base = dict(name="tiny-tr", n_layers=2, d_model=32, n_heads=4,
                n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=64,
                period=(BlockCfg(),), remat=False)
    base.update(kw)
    return ArchConfig(**base)


def _params(cfg):
    if cfg.name not in _PARAMS:
        _PARAMS[cfg.name] = init_params(cfg, jax.random.PRNGKey(0))
    return _PARAMS[cfg.name]


def _engine(cfg=None, **kw):
    cfg = cfg or _cfg()
    scfg = dict(n_slots=2, max_len=48, page_size=4, prefill_chunk=8,
                attn_backend="xla_paged_decode")
    scfg.update(kw)
    return PagedServingEngine(cfg, _params(cfg), ServeConfig(**scfg))


def _reqs(lens, max_new=4):
    return [Request(rid=i, prompt=jax.random.randint(
        jax.random.PRNGKey(20 + i), (lp,), 0, 64), max_new_tokens=max_new)
        for i, lp in enumerate(lens)]


def _profiled_ticks(eng, path, n):
    """Tick ``n`` times under the profiler; the ``engine/`` host spans as
    (name, start, end, stats)."""
    with jax.profiler.trace(str(path)):
        for _ in range(n):
            eng.tick()
    pd = ProfileData.from_file(
        glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True)[0])
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine/"):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda sp: sp[1])


# ---------------------------------------------------------------------------
# host-phase spans
# ---------------------------------------------------------------------------

class TestPhaseSpans:
    def test_phases_nest_inside_tick_on_the_profiler_clock(self, tmp_path):
        eng = _engine()
        for r in _reqs([20, 7]):
            eng.submit(r)
        eng.tick()                           # compile outside the trace
        spans = _profiled_ticks(eng, tmp_path, 4)
        ticks = [sp for sp in spans if sp[0] == "engine/tick"]
        assert len(ticks) == 4
        phases = [sp for sp in spans if sp[0] != "engine/tick"]
        # the 7-token prompt fits one chunk: one chunk step, like any other
        assert {sp[0] for sp in phases} == PHASES
        for name, s, e, _ in phases:
            assert any(ts <= s and e <= te for _, ts, te, _ in ticks), name

    def test_prefill_chunk_span_carries_its_chunk(self, tmp_path):
        eng = _engine()
        eng.submit(_reqs([20])[0])
        spans = _profiled_ticks(eng, tmp_path, 3)
        chunks = [sp[3] for sp in spans if sp[0] == "engine/prefill_chunk"]
        assert [(c["rid"], c["start"], c["valid"]) for c in chunks] == \
            [(0, 0, 8), (0, 8, 8), (0, 16, 4)]

    def test_chrome_trace_pairs_phases_apart_from_decode_tick(self):
        # a pool of 9 pages for 3 slots preempts: phases torn mid-tick
        # still pair, and decode_tick keeps the engine track
        eng = _engine(n_slots=3, max_len=32, n_pages=9, prefill_chunk=64)
        for r in _reqs([12, 12, 12], max_new=6):
            eng.submit(r)
        eng.run_until_drained(max_ticks=2000)
        assert eng.stats["preemptions"] > 0
        tr = eng.telemetry.trace
        tr.validate()
        phase = [e for e in tr.events if e["name"].startswith("engine/")]
        assert phase and all(e["pid"] == PHASE_PID for e in phase)
        assert {e["name"] for e in phase} >= {"engine/tick",
                                               "engine/prefill_chunk"}
        assert all(e["pid"] != PHASE_PID for e in tr.events
                   if e["name"] == "decode_tick")

    def test_telemetry_off_records_no_span(self, tmp_path):
        eng = _engine(telemetry=False)
        for r in _reqs([20, 7]):
            eng.submit(r)
        eng.tick()
        assert _profiled_ticks(eng, tmp_path, 3) == []
        assert eng.telemetry.trace.events == []

    def test_span_facade(self):
        off = Telemetry(enabled=False)
        assert off.span("engine/a") is off.span("engine/b", rid=1)
        with off.span("engine/a"):
            pass
        assert off.trace.events == []
        on = Telemetry(enabled=True)
        with pytest.raises(RuntimeError):
            with on.span("engine/outer", rid=3):
                with on.span("engine/inner"):
                    raise RuntimeError("torn")
        on.trace.validate()
        assert [(e["ph"], e["name"]) for e in on.trace.events] == [
            ("B", "engine/outer"), ("B", "engine/inner"),
            ("E", "engine/inner"), ("E", "engine/outer")]
        assert on.trace.events[0]["args"] == {"rid": 3}


# ---------------------------------------------------------------------------
# the decode counter
# ---------------------------------------------------------------------------

def _decode_ticks(eng):
    return [e["args"] for e in eng.telemetry.trace.events
            if e["name"] == "decode_tick" and e["ph"] == "B"]


def test_decode_tick_counts_live_and_visited_pages():
    eng = _engine(n_slots=3)
    for r in _reqs([20, 7, 13], max_new=6):
        eng.submit(r)
    eng.run_until_drained(max_ticks=200)
    ticks = _decode_ticks(eng)
    assert ticks
    for a in ticks:
        # the kernel copies the live pages, plus one null page for each
        # inactive row, and never more than the whole tables
        assert (a["n_active"] <= a["pages_live"] <= a["pages_grid"]
                <= 3 * eng.pages_per_seq)
        assert a["pages_grid"] == a["pages_live"] + 3 - a["n_active"]
    # a 20-token prompt's first decode step writes slot 20: 6 pages of 4
    assert max(a["pages_live"] for a in ticks) >= 6


def test_pages_grid_is_what_the_kernel_copies():
    """``pages_grid`` is ``pages_visited`` of the lengths the decode step
    is handed (it attends over ``kv_len + 1`` slots), with the Pallas
    kernel serving the batch (interpret mode on the CPU)."""
    eng = _engine(n_slots=3, attn_backend="pallas_paged_decode")
    seen = []
    decode = eng._decode

    def spy(params, cache, pos_pages, tables, kv_len, *rest):
        seen.append(np.asarray(kv_len))
        return decode(params, cache, pos_pages, tables, kv_len, *rest)

    eng._decode = spy
    for r in _reqs([20, 7], max_new=4):
        eng.submit(r)
    eng.run_until_drained(max_ticks=200)
    ticks = _decode_ticks(eng)
    assert ticks and len(ticks) == len(seen)
    for a, kv_len in zip(ticks, seen):
        assert a["pages_grid"] == pages_visited(kv_len + 1, eng.page_size)
        assert a["pages_live"] <= a["pages_grid"] <= 3 * eng.pages_per_seq


# ---------------------------------------------------------------------------
# program names and stages
# ---------------------------------------------------------------------------

def _lowered(eng, which, params=None):
    cfg = eng.cfg
    p = eng.params if params is None else params
    n, P = eng.scfg.n_slots, eng.pages_per_seq
    cs = eng.scfg.prefill_chunk

    def i32(v):
        return jnp.asarray(v, jnp.int32)

    if which == "decode":
        return eng._decode.lower(p, eng.cache, eng.pos_pages,
                                 jnp.zeros((n, P), jnp.int32),
                                 jnp.zeros((n,), jnp.int32),
                                 jnp.zeros((n,), jnp.int32),
                                 jnp.zeros((n, 1), jnp.int32))
    if which == "chunk":
        return eng._chunk.lower(p, eng.cache, eng.pos_pages,
                                jnp.zeros((P,), jnp.int32), i32(0),
                                jnp.zeros((1, cs), jnp.int32), i32(cs))
    if which == "compact":
        return eng._compact.lower(eng.cache, eng.pos_pages,
                                  jnp.zeros((P,), jnp.int32),
                                  jnp.zeros((P * eng.page_size,), bool))
    if which == "sampler":
        return sample_tokens.lower(None, jnp.zeros((n, cfg.vocab_size)),
                                   greedy=True, temperature=1.0)
    assert which == "chunk_spls"
    cq = cs if eng._cap_q is not None else None
    return eng._get_chunk_spls(cq, cq, None, False).lower(
        p, eng.cache, eng.pred_cache, eng.pos_pages,
        jnp.zeros((P,), jnp.int32), i32(0), jnp.zeros((1, cs), jnp.int32),
        i32(cs), i32(4))


@pytest.mark.parametrize("which,name,spls", [
    ("decode", "jit_paged_decode_step", False),
    ("chunk", "jit_paged_prefill_chunk", False),
    ("compact", "jit_compact_slots", True),
    ("chunk_spls", "jit_paged_prefill_chunk_spls", True),
    ("sampler", "jit_sample_tokens", False),
])
def test_programs_are_named_by_their_function(which, name, spls):
    eng = _engine(_cfg(spls))
    text = _lowered(eng, which).as_text()
    assert text.startswith(f"module @{name} "), text[:80]


@pytest.mark.parametrize("which", ["decode", "chunk"])
def test_steps_carry_every_stage_in_their_op_metadata(which):
    # float32 weights computed in bfloat16, as served
    cfg = _cfg(name="tiny-tr-bf16", compute_dtype="bfloat16")
    hlo = _lowered(_engine(cfg), which).compile().as_text()
    # plan: the SPLS step's; moe: a model with held experts' (below)
    for st in set(STAGES) - {"plan", "moe"}:
        assert f"/{st}/" in hlo, st
    # the layer scan carries the pool: its page writes inside the scan
    # body are kv_pool ops, and the scan itself moves no pool data
    assert re.search(r"/while/body/[^\"]*/kv_pool/scatter", hlo)
    assert "/kv_pool/while/" not in hlo


def _moe_cfg():
    return _cfg(name="tiny-tr-moe", compute_dtype="bfloat16",
                period=(BlockCfg(use_moe=True),), moe_experts=8, moe_topk=2,
                moe_held=(0, 1, 2, 3))


@pytest.mark.parametrize("which", ["decode", "chunk"])
def test_moe_steps_carry_the_moe_stage_inside_ffn(which):
    hlo = _lowered(_engine(_moe_cfg()), which).compile().as_text()
    assert "/ffn/moe/" in hlo
    assert "moe_gmm" in hlo


# ---------------------------------------------------------------------------
# weights held at compute width
# ---------------------------------------------------------------------------

_SERVED = {"dense": lambda: _cfg(name="tiny-tr-bf16",
                                 compute_dtype="bfloat16"),
           "moe": _moe_cfg}


def _is_router(path):
    return any(getattr(k, "key", None) == "router" for k in path)


def _float_leaves(params):
    return [(path, a) for path, a
            in jax.tree_util.tree_flatten_with_path(params)[0]
            if jnp.issubdtype(a.dtype, jnp.floating)]


@pytest.mark.parametrize("model", sorted(_SERVED))
def test_engine_holds_its_weights_at_compute_width(model):
    cfg = _SERVED[model]()
    eng = _engine(cfg)
    routers = 0
    for path, a in _float_leaves(eng.params):
        if _is_router(path):
            routers += 1
            assert a.dtype == jnp.float32, jax.tree_util.keystr(path)
        else:
            assert a.dtype == jnp.bfloat16, jax.tree_util.keystr(path)
    assert routers == (model == "moe")
    # the caller's float32 tree is left as it was
    assert all(a.dtype == jnp.float32 for _, a in _float_leaves(_params(cfg)))


def _weight_converts(text, params):
    """The f32 -> bf16 converts in a lowered program whose operand has
    the shape of a parameter, whole or as one layer's slice."""
    shapes = set()
    for path, a in _float_leaves(params):
        shapes.add(a.shape)
        if getattr(path[0], "key", None) == "periods":
            shapes.add(a.shape[1:])
    dims = {"x".join(map(str, s)) for s in shapes}
    return [m.group(0) for m in re.finditer(
        r"stablehlo\.convert %\S+ : \(tensor<((?:\d+x)*)f32>\) -> "
        r"tensor<(?:\d+x)*bf16>", text)
        if m.group(1).rstrip("x") in dims]


@pytest.mark.parametrize("which", ["decode", "chunk"])
@pytest.mark.parametrize("model", sorted(_SERVED))
def test_steps_convert_no_weight(model, which):
    cfg = _SERVED[model]()
    # 3 slots: no activation shares a shape with the stacked (2, 32) gains
    eng = _engine(cfg, n_slots=3)
    text = _lowered(eng, which).as_text()
    assert "stablehlo.convert" in text      # the activations' casts stay
    assert _weight_converts(text, _params(cfg)) == []
    # the same probe finds the casts the steps made before
    cast = jax.jit(lambda p: cast_compute(p, jnp.bfloat16))
    assert _weight_converts(cast.lower(_params(cfg)).as_text(),
                            _params(cfg))


@pytest.mark.parametrize("which", ["decode", "chunk"])
@pytest.mark.parametrize("model", sorted(_SERVED))
def test_steps_refuse_float32_weights(model, which):
    cfg = _SERVED[model]()
    eng = _engine(cfg)
    with pytest.raises(ValueError, match="compute dtype bfloat16"):
        _lowered(eng, which, params=_params(cfg))


def test_engine_gauges_its_resident_weights():
    eng = _engine(_moe_cfg())
    m = eng.telemetry.metrics
    routers = sum(a.size for path, a in _float_leaves(eng.params)
                  if _is_router(path))
    bf16 = sum(a.size for path, a in _float_leaves(eng.params)
               if not _is_router(path))
    assert routers and bf16
    assert m.get("weights/f32_bytes").value == 4 * routers
    assert m.get("weights/bytes").value == 4 * routers + 2 * bf16


def test_packed_pallas_chunk_step_names_the_gather_schedule():
    eng = _engine(_cfg(spls=True), compute_backend="packed_pallas",
                  capacity_buckets=(8,))
    hlo = _lowered(eng, "chunk_spls").compile().as_text()
    assert "gathered_matmul/buffered" in hlo
    assert "/plan/" in hlo

