"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time, per-op device time, and the longest
idle gaps named by what the host was doing.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO op.  Host spans are the benchmark's own
``jax.profiler.TraceAnnotation`` events, named ``bench/...``, on the host
plane.  Both carry nanosecond starts on the profiler's common clock.
"""

from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

HOST_PREFIX = "bench/"
# ops that only contain other ops: their time is their body's
CONTAINERS = {"while", "conditional", "call"}
_OP_NAME = re.compile(r"^%?([\w.\-]+)\s*=")


@dataclasses.dataclass
class Op:
    name: str        # op_label of the event
    start: float     # ns
    dur: float       # ns


@dataclasses.dataclass
class Reduced:
    window: Tuple[float, float]              # ns
    busy_ns: float                           # mean over devices
    op_ns: Dict[str, float]                  # op label -> summed ns
    gaps: List[Tuple[str, float]]            # (host span, ns), longest first
    devices: int

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def ns_matching(self, needle: str) -> float:
        return sum(v for k, v in self.op_ns.items() if needle in k)


def op_label(text: str) -> str:
    """A short, stable label of an HLO op event (the event's name is the
    op's HLO text): the op name with its numeric suffix dropped
    (``fusion.123`` -> ``fusion``).  A Pallas kernel's op is named after
    the kernel (``paged_flash_decode.4``)."""
    m = _OP_NAME.match(text)
    name = m.group(1) if m else text.split(" ", 1)[0]
    return re.sub(r"\.\d+$", "", name)


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def device_ops(pd) -> Dict[str, List[Op]]:
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                ops.append(Op(op_label(ev.name), ev.start_ns,
                              ev.duration_ns))
        out[plane.name] = ops
    return out


def host_spans(pd, prefix: str = HOST_PREFIX) -> List[Tuple[str, float, float]]:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def reduce(pd, window: Optional[Tuple[float, float]] = None,
           window_span: str = HOST_PREFIX + "window",
           devices: Optional[int] = None, top: int = 10) -> Reduced:
    """Busy and idle time of the first ``devices`` TPU planes inside
    ``window`` (ns), or inside the host span named ``window_span``."""
    spans = host_spans(pd)
    if window is None:
        ws = [(s, e) for n, s, e in spans if n == window_span]
        if not ws:
            raise ValueError(f"no host span {window_span!r} in the trace")
        window = (min(s for s, _ in ws), max(e for _, e in ws))
    lo, hi = window
    per_dev = device_ops(pd)
    names = sorted(per_dev, key=lambda n: int(n.rsplit(":", 1)[1]))
    if devices is not None:
        names = names[:devices]
    if not names:
        raise ValueError("no TPU device plane in the trace")
    op_ns = collections.defaultdict(float)
    busy_total = 0.0
    gaps = []
    inner = [(n, s, e) for n, s, e in spans if n != window_span]
    for dev in names:
        ops = per_dev[dev]
        iv = _merge(_clip([(o.start, o.start + o.dur) for o in ops], lo, hi))
        busy_total += sum(e - s for s, e in iv)
        for o in ops:
            s, e = max(o.start, lo), min(o.start + o.dur, hi)
            if e > s and o.name not in CONTAINERS:
                op_ns[o.name] += e - s
        if dev != names[0]:
            continue
        edges = [lo] + [x for se in iv for x in se] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((_name_gap(inner, s, e), e - s))
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window=window, busy_ns=busy_total / len(names),
                   op_ns=dict(op_ns), gaps=gaps[:top], devices=len(names))


def _name_gap(spans, s, e) -> str:
    """The host span that overlaps the gap most; of equals, the innermost
    (the shortest)."""
    best, best_key = "host (no span)", (0.0, 0.0)
    for n, hs, he in spans:
        ov = min(e, he) - max(s, hs)
        if ov > 0:
            key = (ov, -(he - hs))
            if key > best_key:
                best, best_key = n, key
    return best


def breakdown(r: Reduced, top: int = 10) -> dict:
    ops = sorted(r.op_ns.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, v / 1e9] for n, v in ops],
            "idle_gaps": [[n, v / 1e9] for n, v in r.gaps[:top]]}
