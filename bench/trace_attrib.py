#!/usr/bin/env python3
"""Attribution of a JAX profiler trace (``.xplane.pb``) to the program's own
names: device time per program and call, device time per stage of the
model step, and device-idle time per host phase.

    python3 bench/trace_attrib.py <file.xplane.pb> [--devices N]

prints the program x stage table and the idle-by-phase table of the window
(the ``bench/window`` host span, or the whole trace where it has none).

* **Programs.**  Each TPU plane's ``XLA Modules`` line holds one event per
  program call, named ``jit_<function>(<program id>)``; a program is named
  without the id.  An op belongs to the call it starts in.
* **Stages.**  Each ``XLA Ops`` event is joined by its text (the op's HLO)
  to the plane's event metadata, whose ``tf_op`` stat holds the op's name
  stack (``jit(paged_decode_step)/kv_pool/while/body/qkv/dot_general``).
  Its stage is the innermost name of the vocabulary (:data:`STAGES`, the
  program's ``jax.named_scope`` names) in that stack.  An op without a
  ``tf_op`` is ``unscoped``; one whose text maps to more than one
  ``tf_op`` in its program is ``ambiguous``; one whose stack holds no
  stage name is ``other``.  ``jax.profiler.ProfileData`` exposes no event
  metadata stats, so a small reader of the protobuf wire format
  (``xplane.proto``) reads them here, with no dependency beyond JAX.
* **Idle by phase.**  Every device-idle gap in the window (on the first
  device) is split by the host spans on the profiler's clock: each piece
  goes to the innermost ``engine/`` span over it, else to the innermost
  ``bench/`` span (the window's own span aside), else to ``host (no
  span)``.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import dataclasses
import os
import re
import struct
import sys
from typing import Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import trace_reduce  # noqa: E402

# the program's stage names (repro.serving.paged_model.STAGES)
STAGES = ("weights_cast", "embed", "qkv", "attention", "kv_pool", "ffn",
          "lm_head", "plan")
UNSCOPED, AMBIGUOUS, OTHER = "unscoped", "ambiguous", "other"
NO_PROGRAM = "(no program)"
NO_SPAN = "host (no span)"
ENGINE_PREFIX = "engine/"
WINDOW_SPAN = trace_reduce.HOST_PREFIX + "window"
_PROGRAM_ID = re.compile(r"^(.*)\((\d+)\)$")


# ---------------------------------------------------------------------------
# xplane.proto, read from the wire
# ---------------------------------------------------------------------------
#
#   XSpace         { repeated XPlane planes = 1; }
#   XPlane         { string name = 2; repeated XLine lines = 3;
#                    map<int64, XEventMetadata> event_metadata = 4;
#                    map<int64, XStatMetadata> stat_metadata = 5; }
#   XEventMetadata { int64 id = 1; string name = 2; repeated XStat stats = 5; }
#   XStatMetadata  { int64 id = 1; string name = 2; }
#   XStat          { int64 metadata_id = 1; oneof value { double = 2;
#                    uint64 = 3; int64 = 4; string = 5; bytes = 6;
#                    uint64 ref_value = 7 (a stat metadata id: its name) } }
#
# A map entry is a message { key = 1; value = 2; }.

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int = 0, hi: Optional[int] = None):
    """(field number, wire type, value) of one message in ``buf[lo:hi]``;
    a length-delimited value is its ``(start, end)`` in ``buf``."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire == 5:
            val, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield field, wire, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    for f, w, v in _fields(buf, *span):
        if f == 2 and w == 2:
            return v
    return None


def _stat(buf, span, stat_names: Dict[int, str]):
    """(stat name, value) of one XStat."""
    mid, val = None, None
    for f, w, v in _fields(buf, *span):
        if f == 1:
            mid = v
        elif f == 2:
            val = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            val = v - (1 << 64) if f == 4 and v >= 1 << 63 else v
        elif f == 5:
            val = _text(buf, v)
        elif f == 6:
            val = bytes(buf[v[0]:v[1]])
        elif f == 7:
            val = stat_names.get(v)
    return stat_names.get(mid), val


def event_metadata(path: str, plane_prefix: str = "/device:"
                   ) -> Dict[str, Dict[str, List[Tuple[Optional[str],
                                                       Optional[str]]]]]:
    """Per plane (by name, those starting with ``plane_prefix``): op text
    -> one ``(program id, tf_op)`` per event-metadata entry of that text.
    ``tf_op`` loses its trailing ``:<type>``; either is None where the
    entry has no such stat."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for field, wire, plane in _fields(buf):
        if field != 1 or wire != 2:
            continue
        name, events, stat_names = None, [], {}
        for f, w, v in _fields(buf, *plane):
            if f == 2 and w == 2:
                name = _text(buf, v)
            elif f == 4 and w == 2:
                events.append(_map_value(buf, v))
            elif f == 5 and w == 2:
                sm = _map_value(buf, v)
                if sm is None:
                    continue
                sid, sname = None, None
                for ff, ww, vv in _fields(buf, *sm):
                    if ff == 1:
                        sid = vv
                    elif ff == 2 and ww == 2:
                        sname = _text(buf, vv)
                stat_names[sid] = sname
        if name is None or not name.startswith(plane_prefix):
            continue
        by_text = collections.defaultdict(list)
        for ev in events:
            if ev is None:
                continue
            text, stats = None, {}
            for f, w, v in _fields(buf, *ev):
                if f == 2 and w == 2:
                    text = _text(buf, v)
                elif f == 5 and w == 2:
                    k, val = _stat(buf, v, stat_names)
                    stats[k] = val
            if text is None:
                continue
            tf_op = stats.get("tf_op")
            if isinstance(tf_op, str):
                tf_op = tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op
            pid = stats.get("program_id")
            by_text[text].append((None if pid is None else str(pid),
                                  tf_op or None))
        out[name] = dict(by_text)
    return out


def tf_op_of(entries: Sequence[Tuple[Optional[str], Optional[str]]],
             program_id: Optional[str] = None) -> Optional[str]:
    """The ``tf_op`` of an op from its text's metadata entries, narrowed
    to its program where the entries say which: None where it has none,
    :data:`AMBIGUOUS` where they disagree."""
    if program_id is not None:
        mine = [e for e in entries if e[0] == program_id]
        entries = mine or entries
    ops = {t for _, t in entries}
    if len(ops) > 1:
        return AMBIGUOUS
    return next(iter(ops), None)


def stage_of(tf_op: Optional[str], stages: Sequence[str] = STAGES) -> str:
    """The innermost of ``stages`` in a ``tf_op`` name stack."""
    if tf_op is None:
        return UNSCOPED
    if tf_op == AMBIGUOUS:
        return AMBIGUOUS
    for part in reversed(tf_op.split("/")):
        if part in stages:
            return part
    return OTHER


def program_name(module_event_name: str) -> Tuple[str, Optional[str]]:
    """``jit_step(1738)`` -> ``("jit_step", "1738")``."""
    m = _PROGRAM_ID.match(module_event_name)
    return (m.group(1), m.group(2)) if m else (module_event_name, None)


# ---------------------------------------------------------------------------
# the attribution
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Attribution:
    window: Tuple[float, float]                  # ns
    devices: int
    busy_ns: float                               # summed over devices
    covered_ns: float                            # busy, inside a call
    programs: Dict[str, dict]                    # name -> calls, ns
    program_stage_ns: Dict[Tuple[str, str], float]
    op_stage_ns: Dict[Tuple[str, str], float]    # (op label, stage) -> ns
    stage_busy_ns: Dict[str, float]              # union of the stage's ops
    idle_ns: Dict[str, float]                    # phase -> ns (first device)
    ticks: int                                   # engine/tick ends inside

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def coverage(self) -> Optional[float]:
        """Share of the busy time that falls inside a program call."""
        return self.covered_ns / self.busy_ns if self.busy_ns > 0 else None

    def mean_call_ms(self, program: str) -> Optional[float]:
        p = self.programs.get(program)
        if not p or not p["calls"]:
            return None
        return p["ns"] / p["calls"] / 1e6

    def stage_share(self, stage: str) -> Optional[float]:
        """Share (%) of the busy time in ops of ``stage``."""
        if self.busy_ns <= 0:
            return None
        return 100.0 * self.stage_busy_ns.get(stage, 0.0) / self.busy_ns

    @property
    def engine_idle_ns(self) -> float:
        return sum(v for k, v in self.idle_ns.items()
                   if k.startswith(ENGINE_PREFIX))

    def engine_idle_ms_per_tick(self) -> Optional[float]:
        return self.engine_idle_ns / self.ticks / 1e6 if self.ticks else None

    def named_idle_share(self) -> Optional[float]:
        """Share of the idle time in a named phase: an ``engine/`` phase or
        a ``bench/`` span, and neither ``engine/tick`` itself nor no
        span."""
        total = sum(self.idle_ns.values())
        if total <= 0:
            return None
        rest = self.idle_ns.get(ENGINE_PREFIX + "tick", 0.0) \
            + self.idle_ns.get(NO_SPAN, 0.0)
        return (total - rest) / total


def _union_ns(intervals) -> float:
    return sum(e - s for s, e in trace_reduce._merge(intervals))


def _overlap_ns(a, b) -> float:
    """Overlap of two sorted, disjoint interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _phase_segments(spans, lo: float, hi: float):
    """Sorted, disjoint ``(start, end, phase)`` pieces of ``[lo, hi]``:
    the innermost ``engine/`` span over each, else the innermost other
    span, else :data:`NO_SPAN`."""
    edges = sorted({lo, hi} | {x for _, s, e in spans for x in (s, e)
                               if lo < x < hi})
    starts = sorted(spans, key=lambda sp: sp[1])
    active, k, out = [], 0, []
    for a, b in zip(edges, edges[1:]):
        while k < len(starts) and starts[k][1] <= a:
            active.append(starts[k])
            k += 1
        active = [sp for sp in active if sp[2] > a]
        eng = [sp for sp in active if sp[0].startswith(ENGINE_PREFIX)]
        pool = eng or active
        name = min(pool, key=lambda sp: sp[2] - sp[1])[0] if pool \
            else NO_SPAN
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def _idle_by_phase(gaps, segments) -> Dict[str, float]:
    out = collections.defaultdict(float)
    i = 0
    for s, e in gaps:
        while i < len(segments) and segments[i][1] <= s:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < e:
            ov = min(e, segments[j][1]) - max(s, segments[j][0])
            if ov > 0:
                out[segments[j][2]] += ov
            j += 1
    return dict(out)


def attribute(pd, metadata, window: Optional[Tuple[float, float]] = None,
              devices: Optional[int] = None,
              stages: Sequence[str] = STAGES) -> Attribution:
    """The attribution of a loaded trace (``trace_reduce.load``) and its
    :func:`event_metadata`, inside ``window`` (ns; default: the
    ``bench/window`` span, else the whole trace), over the first
    ``devices`` TPU planes."""
    spans = [sp for sp in trace_reduce.host_spans(pd, prefix="")
             if sp[0].startswith((ENGINE_PREFIX,
                                  trace_reduce.HOST_PREFIX))]
    planes = {p.name: p for p in pd.planes
              if p.name.startswith("/device:TPU:")}
    names = sorted(planes, key=lambda n: int(n.rsplit(":", 1)[1]))
    if devices is not None:
        names = names[:devices]
    if not names:
        raise ValueError("no TPU device plane in the trace")
    lines = {n: {ln.name: list(ln.events) for ln in planes[n].lines}
             for n in names}
    if window is None:
        ws = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
        if ws:
            window = (min(s for s, _ in ws), max(e for _, e in ws))
        else:
            ends = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                    for n in names for evs in lines[n].values()
                    for ev in evs] + [(s, e) for _, s, e in spans]
            window = (min(s for s, _ in ends), max(e for _, e in ends))
    lo, hi = window

    programs = collections.defaultdict(lambda: {"calls": 0, "ns": 0.0})
    prog_stage = collections.defaultdict(float)
    op_stage = collections.defaultdict(float)
    stage_iv = collections.defaultdict(list)
    busy = covered = 0.0
    idle: Dict[str, float] = {}
    for dev in names:
        meta = metadata.get(dev, {})
        stage_memo = {}
        calls = sorted(
            (ev.start_ns, ev.start_ns + ev.duration_ns,
             *program_name(ev.name))
            for ev in lines[dev].get("XLA Modules", []))
        for s, e, prog, _ in calls:
            if e > lo and s < hi:
                programs[prog]["calls"] += 1
                programs[prog]["ns"] += min(e, hi) - max(s, lo)
        call_starts = [c[0] for c in calls]
        op_iv = []
        for ev in lines[dev].get("XLA Ops", []):
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            op_iv.append((s, e))
            s, e = max(s, lo), min(e, hi)
            label = trace_reduce.op_label(ev.name)
            if e <= s or label in trace_reduce.CONTAINERS:
                continue
            k = bisect.bisect_right(call_starts, ev.start_ns) - 1
            prog, pid = NO_PROGRAM, None
            if k >= 0 and ev.start_ns < calls[k][1]:
                prog, pid = calls[k][2], calls[k][3]
            st = stage_memo.get((ev.name, pid))
            if st is None:
                st = stage_memo[(ev.name, pid)] = stage_of(
                    tf_op_of(meta.get(ev.name, ()), pid), stages)
            prog_stage[(prog, st)] += e - s
            op_stage[(label, st)] += e - s
            stage_iv[st].append((s, e))
        busy_iv = trace_reduce._merge(trace_reduce._clip(op_iv, lo, hi))
        busy += sum(e - s for s, e in busy_iv)
        call_iv = trace_reduce._merge(trace_reduce._clip(
            [(c[0], c[1]) for c in calls], lo, hi))
        covered += _overlap_ns(busy_iv, call_iv)
        if dev == names[0]:
            edges = [lo] + [x for se in busy_iv for x in se] + [hi]
            gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2])
                    if e > s]
            inner = [sp for sp in spans if sp[0] != WINDOW_SPAN]
            idle = _idle_by_phase(gaps, _phase_segments(inner, lo, hi))
    ticks = sum(1 for n, _, e in spans
                if n == ENGINE_PREFIX + "tick" and lo < e <= hi)
    return Attribution(window=(lo, hi), devices=len(names), busy_ns=busy,
                       covered_ns=covered, programs=dict(programs),
                       program_stage_ns=dict(prog_stage),
                       op_stage_ns=dict(op_stage),
                       stage_busy_ns={k: _union_ns(v)
                                      for k, v in stage_iv.items()},
                       idle_ns=idle, ticks=ticks)


def attribute_file(path: str, window=None, devices=None,
                   stages: Sequence[str] = STAGES) -> Attribution:
    return attribute(trace_reduce.load(path), event_metadata(path),
                     window=window, devices=devices, stages=stages)


# ---------------------------------------------------------------------------
# the operator's tables
# ---------------------------------------------------------------------------

def report(a: Attribution, out=None, top_ops: int = 15) -> None:
    out = out or sys.stdout
    w = a.window_ns / 1e9
    cov = a.coverage
    print(f"window {w:.3f} s, {a.devices} device(s), busy "
          f"{a.busy_ns / 1e9:.3f} s, in a program call "
          f"{100 * cov if cov is not None else float('nan'):.2f}%, "
          f"{a.ticks} engine ticks", file=out)
    stages = sorted({s for _, s in a.program_stage_ns},
                    key=lambda s: -a.stage_busy_ns.get(s, 0.0))
    progs = sorted(a.programs, key=lambda p: -a.programs[p]["ns"])
    extra = sorted({p for p, _ in a.program_stage_ns} - set(progs))
    print("\nprogram x stage (device s; calls, mean ms a call)", file=out)
    head = ["program", "calls", "ms/call", "s"] + stages
    rows = []
    for p in progs + extra:
        info = a.programs.get(p, {"calls": 0, "ns": 0.0})
        mean = a.mean_call_ms(p)
        rows.append([p, str(info["calls"]),
                     f"{mean:.3f}" if mean is not None else "-",
                     f"{info['ns'] / 1e9:.3f}"]
                    + [f"{a.program_stage_ns.get((p, s), 0.0) / 1e9:.3f}"
                       for s in stages])
    rows.append(["stage share of busy (%)", "", "", ""]
                + [f"{a.stage_share(s):.2f}" for s in stages])
    _table(head, rows, out)
    print(f"\nops by device time (top {top_ops}), with their stage",
          file=out)
    ops = sorted(a.op_stage_ns.items(), key=lambda kv: -kv[1])[:top_ops]
    _table(["op", "stage", "s"],
           [[o, s, f"{ns / 1e9:.3f}"] for (o, s), ns in ops], out)
    print("\ndevice idle by host phase (first device)", file=out)
    total = sum(a.idle_ns.values())
    rows = [[k, f"{v / 1e9:.4f}", f"{100 * v / total:.2f}",
             f"{v / a.ticks / 1e6:.3f}" if a.ticks else "-"]
            for k, v in sorted(a.idle_ns.items(), key=lambda kv: -kv[1])]
    rows.append(["total", f"{total / 1e9:.4f}", "100.00",
                 f"{total / a.ticks / 1e6:.3f}" if a.ticks else "-"])
    _table(["phase", "idle s", "% of idle", "ms/tick"], rows, out)
    named = a.named_idle_share()
    eng = a.engine_idle_ms_per_tick()
    print(f"\nidle in a named phase: "
          f"{100 * named if named is not None else float('nan'):.2f}%; "
          f"engine idle per tick: "
          f"{eng if eng is not None else float('nan'):.3f} ms", file=out)


def _table(head, rows, out) -> None:
    width = [max(len(str(r[i])) for r in [head] + rows)
             for i in range(len(head))]
    for r in [head] + rows:
        print("  ".join(str(c).ljust(width[i]) if i == 0
                        else str(c).rjust(width[i])
                        for i, c in enumerate(r)), file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a .xplane.pb file")
    ap.add_argument("--devices", type=int, default=None)
    args = ap.parse_args(argv)
    report(attribute_file(args.trace, devices=args.devices))
    return 0


if __name__ == "__main__":
    sys.exit(main())
