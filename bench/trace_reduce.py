"""Reduce one JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Read with ``jax.profiler.ProfileData``.  Device planes are those named
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
executed operation and the ``XLA Modules`` line one event per executed
program (jitted function), named ``jit_<function>(<id>)``.  The
benchmark's own spans are host events whose names start with
``bench.`` (``jax.profiler.TraceAnnotation``): ``bench.window`` bounds
the traced window, and ``bench.tick`` / ``bench.submit`` / ``bench.wait``
mark what the host was doing.  Host and device events share the
profiler's clock (nanoseconds).

* busy: the union of op intervals inside the window, averaged over the
  device planes used; idle = window - busy.
* program time: summed module durations by program name.
* op time: by ``<program>/<op>`` (the HLO instruction's name without
  its number); loop and call ops are left out, their bodies count.
* idle gaps: the holes in the busy union inside the window, each named
  by the benchmark span that covers its midpoint (``other`` if none).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

Interval = Tuple[float, float]

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)+$")
_HLO = re.compile(r"^%([A-Za-z_][\w\-]*?)(\.\d+)* = ")
#: ops that only hold other ops (their body's ops are on the same line)
_CONTAINERS = ("while", "conditional", "call")


def base_name(name: str) -> str:
    """``jit_branch_segment(12)`` -> ``jit_branch_segment``;
    ``%fusion.123 = bf16[..] fusion(..)`` -> ``fusion``."""
    m = _HLO.match(name)
    return m.group(1) if m else _SUFFIX.sub("", name)


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def total(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def overlap(merged: List[Interval], lo: float, hi: float) -> float:
    return total(clip(merged, lo, hi))


@dataclass
class Reduced:
    """Seconds throughout."""
    window: Interval                        # (start, end)
    devices: int                            # device planes with ops
    busy_s: float                           # mean over devices
    program_s: Dict[str, float]             # module name -> seconds
    program_n: Dict[str, int]               # module name -> executions
    op_s: Dict[str, float]                  # op base name -> seconds
    gaps: List[Tuple[str, float]]           # (span name, seconds)
    spans: Dict[str, List[Interval]] = field(default_factory=dict)
    busy: List[Interval] = field(default_factory=list)   # device 0

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def idle_in(self, name: str) -> Tuple[float, int]:
        """(device-idle seconds inside spans ``name``, span count)."""
        spans = self.spans.get(name, [])
        idle = sum((b - a) - overlap(self.busy, a, b) for a, b in spans)
        return idle, len(spans)

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        by_span: Dict[str, float] = defaultdict(float)
        for name, s in self.gaps:
            by_span[name] += s
        idle = sorted(by_span.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def reduce_profile(pd, window_span: str = "bench.window") -> Reduced:
    """Reduce a ``ProfileData``.  The window is the first
    ``window_span`` host span; without one, the whole device timeline."""
    ns = 1e-9
    spans: Dict[str, List[Interval]] = defaultdict(list)
    dev_ops: Dict[int, List[Tuple[str, float, float]]] = defaultdict(list)
    dev_mods: Dict[int, List[Tuple[str, float, float]]] = defaultdict(list)
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            for ev in line.events:
                a = ev.start_ns * ns
                b = a + ev.duration_ns * ns
                if m is not None:
                    if line.name == "XLA Ops":
                        dev_ops[int(m.group(1))].append((ev.name, a, b))
                    elif line.name == "XLA Modules":
                        dev_mods[int(m.group(1))].append((ev.name, a, b))
                elif ev.name.startswith("bench."):
                    spans[ev.name].append((a, b))
    devices = sorted(d for d in dev_ops if dev_ops[d])
    if spans.get(window_span):
        window = min(spans[window_span])
    else:
        lo = min((a for d in devices for _, a, _ in dev_ops[d]), default=0.0)
        hi = max((b for d in devices for _, _, b in dev_ops[d]), default=0.0)
        window = (lo, hi)
    w0, w1 = window
    busy_by_dev = {d: merge(clip([(a, b) for _, a, b in dev_ops[d]], w0, w1))
                   for d in devices}
    busy_s = (sum(total(v) for v in busy_by_dev.values()) / len(devices)
              if devices else 0.0)
    program_s: Dict[str, float] = defaultdict(float)
    program_n: Dict[str, int] = defaultdict(int)
    for d in devices:
        for name, a, b in dev_mods[d]:
            for a2, b2 in clip([(a, b)], w0, w1):
                program_s[base_name(name)] += b2 - a2
                program_n[base_name(name)] += 1
    op_s: Dict[str, float] = defaultdict(float)
    for d in devices:
        mods = sorted((a, b, base_name(n)) for n, a, b in dev_mods[d])
        starts = [a for a, _, _ in mods]
        for name, a, b in dev_ops[d]:
            op = base_name(name)
            if op in _CONTAINERS:
                continue
            i = bisect.bisect_right(starts, a) - 1
            mod = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
            op_s[f"{mod}/{op}"] += overlap([(a, b)], w0, w1)
    busy0 = busy_by_dev[devices[0]] if devices else []
    gaps: List[Tuple[str, float]] = []
    cursor = w0
    host = {k: merge(clip(v, w0, w1)) for k, v in spans.items()
            if k != window_span}
    for a, b in busy0 + [(w1, w1)]:
        if a > cursor:
            mid = 0.5 * (cursor + a)
            name = next((k for k in ("bench.submit", "bench.tick",
                                     "bench.wait") + tuple(sorted(host))
                         if any(x <= mid < y for x, y in host.get(k, []))),
                        "other")
            gaps.append((name, a - cursor))
        cursor = max(cursor, b)
    return Reduced(window, len(devices), busy_s, dict(program_s),
                   dict(program_n), dict(op_s), gaps,
                   {k: v for k, v in host.items()}, busy0)


def reduce_file(path: str, **kw) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)), **kw)
