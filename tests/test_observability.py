"""Observability subsystem: registry semantics, percentile math vs the
numpy oracle, Chrome-trace pairing on real engine runs (including
preemption unwinding), the telemetry-disabled no-op path, engine.stats
back-compat, and the BENCH_serving.json report schema."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, BlockCfg
from repro.models import init_params
from repro.observability import (CounterDictView, MetricsRegistry,
                                 NullInstrument, RequestRecord, Telemetry,
                                 TraceRecorder, percentile, serving_report,
                                 validate_report, write_report)
from repro.serving import PagedServingEngine, Request, ServeConfig

jax.config.update("jax_platform_name", "cpu")

_PARAMS_CACHE = {}


def _cfg(**kw):
    base = dict(name="tiny", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                head_dim=16, d_ff=64, vocab_size=64, period=(BlockCfg(),),
                remat=False)
    base.update(kw)
    return ArchConfig(**base)


def _params(cfg):
    key = (cfg.name, cfg.period, cfg.spls.enabled)
    if key not in _PARAMS_CACHE:
        _PARAMS_CACHE[key] = init_params(cfg, jax.random.PRNGKey(0))
    return _PARAMS_CACHE[key]


def _reqs(cfg, lens, max_new=5, seed0=0):
    return [Request(rid=i, prompt=jax.random.randint(
        jax.random.PRNGKey(seed0 + i), (lp,), 0, cfg.vocab_size),
        max_new_tokens=max_new) for i, lp in enumerate(lens)]


def _drain(eng, reqs):
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained(max_ticks=2000)
    assert all(r.done for r in reqs)
    return [r.output for r in reqs]


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_gauge_histogram_semantics(self):
        reg = MetricsRegistry()
        c = reg.counter("a/b")
        c.inc()
        c.inc(3)
        assert c.value == 4
        assert reg.counter("a/b") is c          # create-or-return
        g = reg.gauge("g")
        g.set(5.0)
        g.set(2.0)
        g.set(3.0)
        assert g.value == 3.0 and g.high == 5.0 and g.low == 2.0
        h = reg.histogram("h")
        for v in (1.0, 2.0, 3.0):
            h.observe(v)
        assert h.count == 3 and h.mean == 2.0
        snap = reg.snapshot()
        assert snap["a/b"] == 4
        assert snap["g"]["high"] == 5.0
        assert snap["h"]["n"] == 3

    def test_kind_collision_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("x")

    def test_disabled_registry_records_nothing(self):
        reg = MetricsRegistry(enabled=False)
        inst = reg.counter("never")
        assert isinstance(inst, NullInstrument)
        inst.inc()
        reg.histogram("h").observe(1.0)
        reg.gauge("g").set(2.0)
        assert reg.snapshot() == {}
        assert reg.get("never") is None

    def test_histogram_sample_cap_is_visible(self):
        h = MetricsRegistry().histogram("h")
        h.max_samples = 10
        for v in range(25):
            h.observe(float(v))
        assert h.count == 25
        assert len(h.samples) == 10
        assert h.dropped == 15

    def test_injected_clock(self):
        t = [100.0]
        reg = MetricsRegistry(clock=lambda: t[0])
        assert reg.now() == 100.0
        t[0] = 101.5
        assert reg.now() == 101.5

    def test_counter_dict_view_back_compat(self):
        reg = MetricsRegistry()
        view = CounterDictView(reg, "s/", ("a", "b"))
        view["a"] += 1          # the legacy read-then-write idiom
        view["a"] += 2
        view["b"] = 7
        assert view["a"] == 3 and view["b"] == 7
        assert dict(view) == {"a": 3, "b": 7}
        assert reg.counter("s/a").value == 3    # lands on the typed counter
        with pytest.raises(KeyError):
            view["typo"] += 1                   # fixed key set
        with pytest.raises(TypeError):
            del view["a"]


class TestPercentile:
    @pytest.mark.parametrize("n", [1, 2, 5, 37, 100])
    def test_matches_numpy(self, n):
        rng = np.random.RandomState(n)
        vals = list(rng.rand(n) * 10)
        for p in (0.0, 1.0, 13.7, 50.0, 90.0, 99.0, 100.0):
            assert percentile(vals, p) == pytest.approx(
                np.percentile(vals, p), abs=1e-12)

    def test_empty_is_nan(self):
        assert np.isnan(percentile([], 50.0))

    def test_histogram_summary_vs_numpy(self):
        h = MetricsRegistry().histogram("h")
        rng = np.random.RandomState(0)
        vals = rng.rand(200)
        for v in vals:
            h.observe(float(v))
        assert h.percentile(50.0) == pytest.approx(np.percentile(vals, 50))
        assert h.percentile(99.0) == pytest.approx(np.percentile(vals, 99))
        assert h.mean == pytest.approx(vals.mean())


# ---------------------------------------------------------------------------
# trace recorder
# ---------------------------------------------------------------------------

class TestTraceRecorder:
    def test_paired_events_validate(self):
        tr = TraceRecorder()
        tr.begin("request", 0.0, 1)
        tr.begin("prefill", 0.1, 1)
        tr.end("prefill", 0.2, 1)
        tr.instant("first_token", 0.2, 1)
        tr.end("request", 0.3, 1)
        tr.validate()
        chrome = tr.to_chrome_trace()
        assert set(chrome) == {"traceEvents", "displayTimeUnit"}
        assert chrome["traceEvents"][0]["ts"] == 0.0
        assert chrome["traceEvents"][1]["ts"] == pytest.approx(1e5)
        assert [e["ph"] for e in chrome["traceEvents"]] == \
            ["B", "B", "E", "i", "E"]

    def test_validate_rejects_unclosed_and_misnested(self):
        tr = TraceRecorder()
        tr.begin("a", 0.0, 1)
        with pytest.raises(ValueError, match="unclosed"):
            tr.validate()
        tr2 = TraceRecorder()
        tr2.begin("a", 0.0, 1)
        tr2.begin("b", 0.1, 1)
        tr2.events.append({"ph": "E", "name": "a", "ts": 0.2, "pid": 1,
                           "tid": 1})
        with pytest.raises(ValueError, match="nesting"):
            tr2.validate()

    def test_validate_rejects_time_regression(self):
        tr = TraceRecorder()
        tr.begin("a", 1.0, 1)
        tr.end("a", 0.5, 1)
        with pytest.raises(ValueError, match="regress"):
            tr.validate()

    def test_disabled_records_nothing(self):
        tr = TraceRecorder(enabled=False)
        tr.begin("a", 0.0, 1)
        tr.instant("i", 0.1, 1)
        tr.end("a", 0.2, 1)
        assert tr.events == []
        tr.validate()

    def test_deferred_counters_land_in_their_span_args(self, monkeypatch):
        from repro.observability import trace as trace_mod
        tr = TraceRecorder()
        args = {"valid": 8}
        tr.begin("prefill_chunk", 0.0, 1, args=args)
        tr.defer(args, ("pairs", "peak"), jnp.asarray([12, 5], jnp.int32))
        tr.end("prefill_chunk", 0.1, 1)
        assert args == {"valid": 8}            # not read back yet
        assert tr.events[0]["args"] == {"valid": 8, "pairs": 12, "peak": 5}
        # a full batch is read back without waiting for a reader
        monkeypatch.setattr(trace_mod, "MAX_PENDING", 2)
        a2, a3 = {}, {}
        tr.defer(a2, ("n",), jnp.asarray([1]))
        tr.defer(a3, ("n",), jnp.asarray([2]))
        assert (a2, a3) == ({"n": 1}, {"n": 2})
        off = TraceRecorder(enabled=False)
        off.defer(args, ("x",), jnp.asarray([1]))
        assert "x" not in args and off.events == []

    def test_max_events_counts_drops(self):
        tr = TraceRecorder(max_events=3)
        for i in range(5):
            tr.instant("x", float(i), 1)
        assert len(tr.events) == 3 and tr.dropped == 2

    def test_open_span_stack_tracks_nesting(self):
        tr = TraceRecorder()
        tr.begin("request", 0.0, 3)
        tr.begin("prefill", 0.1, 3)
        assert tr.open_spans(3) == ["request", "prefill"]
        tr.end("prefill", 0.2, 3)
        assert tr.open_spans(3) == ["request"]


# ---------------------------------------------------------------------------
# telemetry facade (fake clock)
# ---------------------------------------------------------------------------

class TestTelemetryLifecycle:
    def _tel(self):
        t = {"now": 0.0}

        def clock():
            return t["now"]

        return Telemetry(clock=clock), t

    def test_ttft_tpot_from_injected_clock(self):
        tel, t = self._tel()
        tel.request_submitted(0, prompt_len=8)
        t["now"] = 1.0
        tel.request_admitted(0)
        t["now"] = 2.0
        tel.first_token(0)
        for ts in (2.5, 3.0, 3.5):
            t["now"] = ts
            tel.tokens_decoded([0])
        tel.request_retired(0)
        rec = tel.requests[0]
        assert rec.ttft_s == 2.0            # submit -> first token
        assert rec.n_tokens == 4
        assert rec.tpot_s == pytest.approx(0.5)
        assert rec.outcome == "retired"
        tel.trace.validate()

    def test_preemption_unwinds_open_spans(self):
        tel, t = self._tel()
        tel.request_submitted(0, prompt_len=8)
        tel.request_admitted(0)
        tel.span_begin("prefill_chunk", rid=0)
        t["now"] = 1.0
        tel.request_preempted(0)            # struck mid-phase
        t["now"] = 2.0
        tel.request_admitted(0)
        tel.request_retired(0)
        tel.trace.validate()                # B/E pairing survived
        assert tel.requests[0].n_preempts == 1
        assert tel.metrics.counter("requests/requeues").value == 1

    def test_abort_closes_request_span(self):
        tel, t = self._tel()
        tel.request_submitted(0, prompt_len=8)
        tel.request_admitted(0)
        tel.span_begin("full_prefill", rid=0)
        tel.request_aborted(0)
        tel.trace.validate()
        assert tel.requests[0].outcome == "aborted"

    def test_disabled_facade_records_nothing(self):
        tel = Telemetry(enabled=False)
        tel.request_submitted(0, prompt_len=8)
        tel.request_admitted(0)
        tel.first_token(0)
        tel.tokens_decoded([0])
        tel.request_retired(0)
        tel.span_begin("x")
        tel.span_end("x")
        assert tel.requests == {}
        assert tel.metrics.snapshot() == {}
        assert tel.trace.events == []

    def test_record_properties_incomplete(self):
        rec = RequestRecord(rid=0, prompt_len=4, submit_ts=0.0)
        assert rec.ttft_s is None and rec.tpot_s is None


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

class TestEngineTelemetry:
    def test_trace_valid_and_stats_back_compat(self, tmp_path):
        cfg = _cfg()
        params = _params(cfg)
        eng = PagedServingEngine(cfg, params, ServeConfig(
            n_slots=2, max_len=48, page_size=4, prefill_chunk=8,
            attn_backend="xla_paged_decode"))
        _drain(eng, _reqs(cfg, [30, 7, 25]))
        tel = eng.telemetry
        tel.trace.validate()
        # legacy stats counters live (typed instruments underneath)
        assert eng.stats["prefill_chunks"] >= 4
        assert eng.stats["admitted"] == 3
        assert eng.stats["retired"] == 3
        assert tel.core.counter("sched/prefill_chunks").value == \
            eng.stats["prefill_chunks"]
        # every request retired with tokens and a ttft
        assert len(tel.requests) == 3
        for rec in tel.requests.values():
            assert rec.outcome == "retired"
            assert rec.n_tokens == 5
            assert rec.ttft_s is not None and rec.ttft_s >= 0
        # the trace round-trips as Chrome JSON
        path = tmp_path / "trace.json"
        tel.trace.write(str(path))
        chrome = json.loads(path.read_text())
        assert {e["ph"] for e in chrome["traceEvents"]} <= {"B", "E", "i"}
        names = {e["name"] for e in chrome["traceEvents"]}
        assert {"request", "queued", "prefill_chunk", "decode_tick",
                "first_token"} <= names

    def test_preemption_trace_stays_paired(self):
        cfg = _cfg()
        params = _params(cfg)
        eng = PagedServingEngine(cfg, params, ServeConfig(
            n_slots=3, max_len=32, page_size=4, n_pages=9,
            attn_backend="xla_paged_decode"))
        _drain(eng, _reqs(cfg, [12, 12, 12], max_new=6))
        assert eng.stats["preemptions"] > 0
        eng.telemetry.trace.validate()
        report = serving_report(eng)
        assert report["requests"]["preemptions"] > 0
        assert report["requests"]["preemption_rate"] > 0

    def test_telemetry_off_is_bitwise_identical_and_silent(self):
        cfg = _cfg()
        params = _params(cfg)

        def run(telemetry):
            eng = PagedServingEngine(cfg, params, ServeConfig(
                n_slots=2, max_len=48, page_size=4, prefill_chunk=8,
                attn_backend="xla_paged_decode", telemetry=telemetry))
            return _drain(eng, _reqs(cfg, [30, 7, 25])), eng

        on, eng_on = run(True)
        off, eng_off = run(False)
        assert on == off                      # greedy outputs bit-for-bit
        tel = eng_off.telemetry
        assert tel.trace.events == []
        assert tel.metrics.snapshot() == {}
        assert tel.requests == {}
        # back-compat stats stay live either way (always-on core)
        assert eng_off.stats["retired"] == eng_on.stats["retired"] == 3
        assert eng_off.stats["prefill_chunks"] == \
            eng_on.stats["prefill_chunks"]

    def test_pool_gauges_and_guard_counter(self):
        from repro.serving import PagePool

        cfg = _cfg()
        params = _params(cfg)
        eng = PagedServingEngine(cfg, params, ServeConfig(
            n_slots=2, max_len=48, page_size=4, prefill_chunk=8,
            attn_backend="xla_paged_decode"))
        _drain(eng, _reqs(cfg, [30, 7]))
        m = eng.telemetry.metrics
        gauge = m.get("pool/pages_in_use")
        assert gauge is not None
        assert gauge.high >= eng.stats["peak_pages"] - 1  # tick-sampled
        assert gauge.value == 0                           # drained
        assert m.get("pool/guard_trips").value == 0
        assert eng.stats["guard_trips"] == 0
        # the guard itself: a double free raises AND counts
        pool = PagePool(6, 4)
        pages = pool.alloc(2)
        pool.free(pages)
        with pytest.raises(ValueError, match="free"):
            pool.free(pages)
        assert pool.guard_trips == 1


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

class TestReport:
    def _engine_report(self, tmp_path):
        cfg = _cfg()
        params = _params(cfg)
        eng = PagedServingEngine(cfg, params, ServeConfig(
            n_slots=2, max_len=48, page_size=4, prefill_chunk=8,
            attn_backend="xla_paged_decode"))
        _drain(eng, _reqs(cfg, [30, 7, 25]))
        return serving_report(eng, wall_s=1.0)

    def test_schema_valid_and_round_trips(self, tmp_path):
        report = self._engine_report(tmp_path)
        validate_report(report)
        assert report["schema_version"] == 1
        assert report["requests"]["retired"] == 3
        assert report["latency"]["ttft_ms"]["n"] == 3
        assert report["latency"]["tpot_ms"]["p50"] > 0
        assert report["throughput"]["tokens"] == 15
        assert report["throughput"]["goodput_tok_s"] == \
            report["throughput"]["tok_s"]     # nothing aborted
        for c in ("qkv", "kv", "attn", "ffn"):
            assert f"flops_saved_{c}_pct" in report["sparsity"]
        path = tmp_path / "BENCH_serving.json"
        write_report(str(path), report)
        validate_report(json.loads(path.read_text()))

    def test_validator_names_all_problems(self, tmp_path):
        report = self._engine_report(tmp_path)
        del report["latency"]["ttft_ms"]
        report["schema_version"] = 99
        with pytest.raises(ValueError) as ei:
            validate_report(report)
        msg = str(ei.value)
        assert "ttft_ms" in msg and "schema_version 99" in msg

    def test_require_nonzero_flops(self, tmp_path):
        report = self._engine_report(tmp_path)   # dense compute: all 0.0
        validate_report(report)                  # fine without the flag
        with pytest.raises(ValueError, match="flops_saved_qkv_pct"):
            validate_report(report, require_nonzero_flops=True)

    def test_cli_validates(self, tmp_path, capsys):
        from repro.observability.report import main

        report = self._engine_report(tmp_path)
        path = tmp_path / "r.json"
        write_report(str(path), report)
        assert main([str(path)]) == 0
        assert main([str(path), "--require-nonzero-flops"]) == 1
