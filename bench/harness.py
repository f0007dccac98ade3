"""One run of one cell: set-up, the measured window, the check, the line.

The harness owns the clock.  It submits requests (on a schedule in an
open loop, as clients refill in a closed loop), calls the engine's
``tick()``, and stamps the end of every tick; a token is served at the
end of the tick that produced it.  The window opens after set-up and
closes at the end of the first tick that ends ``--seconds`` after it
opened, so every rate divides by the exact span of whole ticks.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import glob
import importlib.util
import itertools
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

import flops
import traffic

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
clock = time.monotonic


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the benchmark's files
# ---------------------------------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    bench: dict
    cell: dict
    conf: dict
    mix: dict

    @classmethod
    def find(cls, name: str, root: str = ROOT) -> "Cell":
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        cell = cells[name]
        confs = {c["name"]: c for c in bench["configs"]}
        conf = load_json(os.path.join(root, confs[cell["config"]]["file"]))
        mix = load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
        return cls(bench, cell, conf, mix)

    def metrics(self, kind: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.bench[kind]
                if "workloads" not in m
                or self.cell["name"] in m["workloads"]]


def module(name: str, where: str = ""):
    """``bench/<where>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, where, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_" + (where + "_" + name).replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """``bench/metrics/<name>.py``'s ``read``."""
    return module(name, "metrics").read


# ---------------------------------------------------------------------------
# what the window records
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Rec:
    rid: int
    prompt: np.ndarray
    max_new: int
    phase: str                      # "warm" | "window"
    due: float                      # monotonic seconds
    submit: float
    req: object = None
    times: List[float] = dataclasses.field(default_factory=list)
    done_t: Optional[float] = None

    @property
    def output(self) -> List[int]:
        return self.req.output


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: Cell
    seed: int
    shape: flops.Shape
    peak: dict
    setup_s: float
    t0: float
    t1: float
    recs: List[Rec]
    ticks: List[float]
    spans: List[dict]
    trace: object = None             # trace_reduce.Reduced

    @property
    def span_s(self) -> float:
        return self.t1 - self.t0

    def in_window(self, t: Optional[float]) -> bool:
        return t is not None and self.t0 < t <= self.t1

    def span_pairs(self, name: str):
        """(begin, end, begin args) of the program's spans called
        ``name``."""
        open_, out = {}, []
        for ev in self.spans:
            if ev["name"] != name:
                continue
            key = ev["tid"]
            if ev["ph"] == "B":
                open_[key] = ev
            elif ev["ph"] == "E" and key in open_:
                b = open_.pop(key)
                out.append((b["ts"], ev["ts"], b.get("args") or {}))
        return out

    def window_spans(self, name: str):
        return [p for p in self.span_pairs(name) if self.in_window(p[1])]

    def decode_tokens(self):
        """(served time, keys attended) of every token a decode step
        produced in the window: token ``k`` (from 0) of a request attends
        to its prompt and ``k`` served tokens."""
        out = []
        for r in self.recs:
            lp = len(r.prompt)
            for k, t in enumerate(r.times):
                if k >= 1 and self.in_window(t):
                    out.append((t, lp + k))
        return out

    def due_in_window(self) -> List[Rec]:
        return [r for r in self.recs
                if r.phase == "window" and self.t0 <= r.due <= self.t1]


# ---------------------------------------------------------------------------
# the load
# ---------------------------------------------------------------------------

class Driver:
    """Submits the traffic and ticks the engine; stamps every tick."""

    def __init__(self, eng, prog, mix: dict, seed: int, vocab: int,
                 annotate):
        self.eng, self.prog, self.mix = eng, prog, mix
        self.seed, self.vocab = seed, vocab
        self.annotate = annotate
        self.closed = mix["arrival"]["kind"] == "closed"
        self.inflight: Dict[int, Rec] = {}
        self.recs: List[Rec] = []
        self.ticks: List[float] = []
        self.pending = collections.deque()     # (due, spec, phase)
        self.queue = None                      # closed loop: the set, cycled
        self.phase = "warm"
        self.next_rid = 0

    # -- arrivals ------------------------------------------------------
    def open_schedule(self, specs, t_base: float, phase: str) -> None:
        self.pending.clear()
        for s in specs:
            self.pending.append((t_base + s.due, s, phase))

    def closed_queue(self, specs) -> None:
        self.queue = itertools.cycle(specs)

    def _submit(self, spec, due: float, phase: str) -> None:
        rid = self.next_rid
        self.next_rid += 1
        now = clock()
        rec = Rec(rid=rid, prompt=spec.prompt, max_new=spec.max_new,
                  phase=phase, due=due, submit=now)
        rec.req = self.prog.request(rid, spec.prompt, spec.max_new)
        with self.annotate("bench/submit"):
            self.eng.submit(rec.req)
        self.recs.append(rec)
        self.inflight[rid] = rec

    def _submit_due(self, now: float) -> None:
        if self.closed:
            while len(self.inflight) < self.mix["arrival"]["clients"]:
                self._submit(next(self.queue), now, self.phase)
            return
        while self.pending and self.pending[0][0] <= now:
            due, spec, phase = self.pending.popleft()
            self._submit(spec, due, phase)

    # -- ticks ---------------------------------------------------------
    def _stamp(self, t: float) -> None:
        for rid in list(self.inflight):
            rec = self.inflight[rid]
            n = len(rec.req.output)
            if n > len(rec.times):
                rec.times.extend([t] * (n - len(rec.times)))
            if rec.req.done:
                rec.done_t = t
                del self.inflight[rid]

    def run(self, t_end: float, submit: bool = True, stop=None) -> float:
        """Tick until a tick ends at or after ``t_end`` (or ``stop()``);
        returns the end of the last tick."""
        while True:
            now = clock()
            if submit:
                self._submit_due(now)
            if not self.inflight:
                if not submit or (not self.closed and not self.pending):
                    return now
                nxt = self.pending[0][0] if self.pending else t_end
                if min(nxt, t_end) > now:
                    with self.annotate("bench/wait"):
                        time.sleep(min(nxt, t_end) - now)
                if clock() >= t_end:
                    return clock()
                continue
            with self.annotate("bench/tick"):
                self.eng.tick()
            t = clock()
            self.ticks.append(t)
            self._stamp(t)
            if t >= t_end or (stop is not None and stop()):
                return t


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def warm_up(eng, prog, conf, mix, seed, vocab) -> dict:
    """One request through every program the cell's traffic reaches: two
    chunk steps (a prompt longer than one chunk) and decode."""
    n = 2 * conf["serve"]["prefill_chunk"] + 1
    rng = traffic.rng_for(seed, 5)
    req = prog.request(10 ** 9, traffic.make_tokens(mix["tokens"], n, vocab,
                                                    rng), 2)
    eng.submit(req)
    ticks = 0
    while not req.done:
        eng.tick()
        ticks += 1
        if ticks > 16:
            raise RuntimeError("warm-up request did not finish")
    return {"ticks": ticks, "prompt": n}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts JAX's compile events (backend compiles and persistent-cache
    loads) and sums their seconds; one per process (:func:`compile_counter`)."""

    def __init__(self):
        import jax

        self.events = collections.Counter()
        self.seconds = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/") or \
                event.startswith("/jax/compilation_cache/"):
            self.events[event] += 1
            self.seconds[event] += duration

    def snapshot(self):
        return collections.Counter(self.events), \
            collections.Counter(self.seconds)


_COUNTER = []


def compile_counter() -> CompileCounter:
    if not _COUNTER:
        _COUNTER.append(CompileCounter())
    return _COUNTER[0]


def device_info(jax, n: int) -> dict:
    devs = jax.devices()[:n]
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def judge(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    """The verdict on one set of compared numbers: each has a value at or
    under its limit.  The program's numbers and the control's are judged
    alike."""
    return bool(nums) and all(
        nums.get(name) is not None and limit is not None
        and nums[name] <= limit for name, limit in limits.items())


def memory_peak(jax, n: int) -> Optional[int]:
    peaks = []
    for d in jax.devices()[:n]:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             control: bool = False, fault=None,
             check_outputs: bool = True, keep: Optional[dict] = None
             ) -> dict:
    """The whole run; returns the result line's object.

    The other arguments serve the tools beside the benchmark, never its
    own runs: ``control`` also reads the fp8 control on the same sample;
    ``fault`` is called with the engine before the window to break it;
    ``check_outputs=False`` skips the reference; ``keep`` receives the
    :class:`Run`."""
    import program as prog

    prog.import_program()
    cache_dir = prog.configure_compile_cache()
    import jax

    import check

    chips = cell.cell["chips"]
    device = device_info(jax, chips)
    if require_tpu and (device["platform"] != "tpu"
                        or len(jax.devices()) < chips):
        raise SystemExit(f"bench: needs {chips} TPU chip(s), JAX found "
                         f"{len(jax.devices())} {device['platform']} "
                         f"device(s)")
    peaks = load_json(os.path.join(BENCH, "peaks.json"))
    if require_tpu and device["kind"] not in peaks:
        raise SystemExit(f"bench: no peaks for device {device['kind']!r}")
    peak = peaks.get(device["kind"], {})
    log(f"[device] {device} jax={jax.__version__} cache={cache_dir}")

    conf, mix = cell.conf, cell.mix
    wgen = module(conf["weights"])
    ref = module(conf["reference"])
    model = conf["model"]
    shape = flops.Shape.from_model(model)
    compiles = compile_counter()
    cfg = prog.serving_config(conf)
    w = jax.block_until_ready(wgen.make_weights(model, seed))
    prog.check_layout(cfg, w)
    eng = prog.build_engine(cfg, w, conf["serve"])
    vocab = model["vocab_size"]
    t = clock()
    info = warm_up(eng, prog, conf, mix, seed, vocab)
    log(f"[setup] warm-up: {info} in {clock() - t:.1f}s")
    if fault is not None:
        fault(eng)

    annotate = jax.profiler.TraceAnnotation
    drv = Driver(eng, prog, mix, seed, vocab, annotate)
    closed = drv.closed
    if closed:
        drv.closed_queue(traffic.request_set(mix, seed, vocab, stream=1))
    warm_s = float(mix.get("warm_s", 0))
    if warm_s > 0:
        if closed:
            drv.run(clock() + warm_s)
        else:
            drv.open_schedule(traffic.request_set(mix, seed, vocab, stream=2,
                                                  seconds=warm_s),
                              clock(), "warm")
            drv.run(clock() + warm_s)
    window_specs = None if closed else traffic.request_set(
        mix, seed, vocab, stream=1, seconds=seconds)

    tmp = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
    aborted0 = prog.aborted(eng)
    c_ev0, c_s0 = compiles.snapshot()

    t0 = clock()
    setup_s = t0 - t_start
    drv.phase = "window"
    if not closed:
        drv.open_schedule(window_specs, t0, "window")
    with annotate("bench/window"):
        t1 = drv.run(t0 + seconds)

    c_ev1, c_s1 = compiles.snapshot()
    if trace:
        jax.profiler.stop_trace()
    failed = prog.aborted(eng) - aborted0
    mem = memory_peak(jax, chips)
    in_window = {k: c_ev1[k] - c_ev0[k] for k in c_ev1 if c_ev1[k] - c_ev0[k]}
    log(f"[window] {t1 - t0:.3f}s, {sum(1 for x in drv.ticks if t0 < x <= t1)}"
        f" ticks; compile events inside: {in_window or 0} "
        f"({sum(c_s1.values()) - sum(c_s0.values()):.3f}s)")
    late = [r.submit - r.due for r in drv.recs if r.phase == "window"]
    if late:
        log(f"[window] generator lateness (submit - due): mean "
            f"{1e3 * float(np.mean(late)):.3f} ms, max "
            f"{1e3 * float(np.max(late)):.3f} ms over {len(late)} requests")

    # the check's pool: requests the window finished; where too few
    # were, the engine serves on (untimed, nothing new) until enough are
    chk = conf["check"]
    done = lambda: [r for r in drv.recs if r.done_t is not None
                    and r.done_t > t0]
    if len(done()) < chk["min_requests"]:
        t_d = clock()
        drv.run(t_d + chk["drain_s"], submit=False,
                stop=lambda: len(done()) >= chk["min_requests"])
        log(f"[check] served {clock() - t_d:.1f}s past the close for "
            f"{len(done())} finished requests")

    run = Run(cell=cell, seed=seed, shape=shape, peak=peak, setup_s=setup_s,
              t0=t0, t1=t1, recs=drv.recs, ticks=drv.ticks,
              spans=prog.spans(eng))
    finished = done()
    attempted = len(run.due_in_window()) if not closed else \
        sum(1 for r in drv.recs if r.phase == "window")

    # free the program's state before the reference runs
    del drv, eng
    gc.collect()

    if keep is not None:
        keep["run"] = run
    if not check_outputs:
        finished = []
    picked = check.sample(finished, chk["requests"], seed)
    t_c = clock()
    view = wgen.layers(w)
    g = check.gaps(ref, view, model, picked, conf["serve"]["max_len"],
                   traffic.max_output(mix), control=control)
    nums = check.numbers(g["served"])
    log(f"[check] {len(picked)} requests, {g['served'].size} served tokens,"
        f" reference {clock() - t_c:.1f}s: "
        + " ".join(f"{k}={v!r}" for k, v in nums.items()))
    result_checks = {"failed_requests": {"value": failed, "limit": 0}}
    for name, limit in chk["limits"].items():
        result_checks[name] = {"value": nums.get(name), "limit": limit}
    ok = judge(nums, chk["limits"]) and failed == 0
    if control:
        cnums = check.numbers(g["control"])
        cok = judge(cnums, chk["limits"])
        result_checks["control"] = dict(cnums, correct=cok)
        log("[control] fp8 reference in the program's place: "
            + " ".join(f"{k}={v!r}" for k, v in cnums.items())
            + f" -> correct={cok}")

    if trace:
        import trace_reduce

        t_r = clock()
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        run.trace = trace_reduce.reduce(trace_reduce.load(path),
                                        devices=chips)
        shutil.rmtree(tmp, ignore_errors=True)
        log(f"[trace] reduced in {clock() - t_r:.1f}s: busy "
            f"{run.trace.busy_s:.3f}s of {run.trace.window_s:.3f}s")

    metrics = {}
    kind = "per_layer" if trace else "end_to_end"
    for m in cell.metrics(kind):
        val = reader(m["name"])(run)
        if val is None:
            if kind == "end_to_end":
                raise RuntimeError(f"end-to-end metric {m['name']} has no "
                                   f"value")
            continue
        metrics[m["name"]] = {"value": val, "unit": m["unit"]}

    device["memory_peak_bytes"] = mem
    out = {"correct": ok, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        import trace_reduce

        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = trace_reduce.breakdown(run.trace)
    out["checks"] = result_checks
    for name, c in result_checks.items():
        if name != "control":
            log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    return out
