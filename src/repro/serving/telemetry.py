"""Serving-tier observability: request-lifecycle tracing + a metrics
registry — zero-overhead when disabled.

Seven PRs of serving machinery (scheduler -> policies -> packing -> trunk
cache -> faults -> kernels) report through one end-of-run ``summary()``
dict.  That answers *how much* but never *why*: which request missed its
deadline behind which backlog, which pack bucket carried the pad waste,
which cache lookups fell to the spill tier.  This module adds the two
primitives that answer those questions without perturbing anything:

:class:`Tracer`
    Structured lifecycle spans (``request.submit -> request.admit ->
    request.group -> group.hold -> group.launch -> request.queue ->
    cache.{exact,ann,miss} -> phase.shared -> group.fork -> phase.branch
    -> request.complete`` / ``group.preempt`` / ``group.resume`` — see
    docs/architecture.md §11 for the full taxonomy) plus exec-lane spans
    of the host's own work (the tick and its admit / launch / advance /
    complete phases, pack build and scatter, each segment dispatch, the
    text tower's dispatch and wait, the completion fetch), exportable as
    Chrome trace-event JSON (loadable in Perfetto / chrome://tracing).
    Lifecycle timestamps are the scheduler's injectable ``now``; exec
    spans read the tracer's own ``clock`` (``time.perf_counter`` unless
    a test injects one) and are also written into the JAX profiler's
    trace as ``sage.<name>`` annotations, so device-idle time can be
    laid against the host phase that caused it.

:class:`MetricsRegistry`
    The single home of the serving stats: counters live in
    :class:`StatGroup` objects — real ``dict`` subclasses, so existing
    ``stats["nfe"] += x`` call sites and every test that reads
    ``sched.stats`` / ``cache.stats`` keep working unchanged at zero
    added cost — plus callable gauges, labeled counter families (per-QoS
    mirrors, per-kind fault counts) and fixed-bucket histograms
    (latency / queue depth / pack occupancy).  ``to_prometheus()`` emits
    the text exposition format (``--metrics out.prom`` in
    ``examples/serve_shared.py``); naming is ``sage_<group>_<key>`` with
    ``_total`` suffixed to counters.

Neither primitive touches a device value, an RNG stream, or anything
the sampler sees: tracing enabled or disabled is bitwise-invisible to
results (``tests/test_telemetry.py`` pins this), and with
``tracer=None`` (the default) the scheduler's emit sites reduce to one
``is not None`` branch: no clock read, no annotation.

:func:`safe_ratio` is the shared divide-by-zero guard for every derived
rate (``launches_per_tick``, ``pad_waste``, hit rates): zero-tick /
zero-row runs uniformly report the default (0.0), never NaN, inf, or a
per-call-site sentinel style.
"""
from __future__ import annotations

import bisect
import json
import time
from collections import Counter, OrderedDict
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, Iterable, List, Mapping,
                    Optional, Sequence, Tuple)

from jax.profiler import TraceAnnotation

__all__ = ["safe_ratio", "Histogram", "StatGroup", "MetricsRegistry",
           "Tracer", "TraceEvent", "LATENCY_BUCKETS",
           "QUEUE_DEPTH_BUCKETS", "OCCUPANCY_BUCKETS",
           "PID_REQUESTS", "PID_GROUPS", "PID_EXEC"]


def safe_ratio(num: float, den: float, default: float = 0.0) -> float:
    """``num / den`` with an explicit empty-denominator answer.

    THE divide-by-zero convention for derived serving stats: a rate over
    nothing is ``default`` (0.0 unless stated), never NaN/inf and never
    a mixed bag of per-call-site sentinels."""
    return num / den if den else default


# -- fixed histogram bucket sets (upper bounds; +Inf is implicit) -------
# latencies are virtual ticks (1 tick = 1 time unit on the virtual
# clock); queue depth is waiting requests at tick start; occupancy is
# members/group_size at launch (1.0 = a full group)
LATENCY_BUCKETS: Tuple[float, ...] = (1, 2, 4, 8, 16, 32, 64, 128)
QUEUE_DEPTH_BUCKETS: Tuple[float, ...] = (0, 1, 2, 4, 8, 16, 32, 64)
OCCUPANCY_BUCKETS: Tuple[float, ...] = (0.25, 0.5, 0.75, 1.0)


class Histogram:
    """Fixed-bucket cumulative histogram (Prometheus semantics).

    ``buckets`` are upper bounds in increasing order; observations above
    the last bound land only in the implicit +Inf bucket.  ``observe``
    is O(log buckets) pure python — cheap enough to stay always-on next
    to the stat deques it summarises."""

    __slots__ = ("buckets", "counts", "total", "sum")

    def __init__(self, buckets: Sequence[float]):
        b = tuple(float(x) for x in buckets)
        if list(b) != sorted(set(b)):
            raise ValueError(f"buckets must be strictly increasing: {b}")
        self.buckets = b
        self.counts = [0] * len(b)        # per-bound cumulative-at-export
        self.total = 0
        self.sum = 0.0

    def observe(self, v: float) -> None:
        i = bisect.bisect_left(self.buckets, v)
        if i < len(self.counts):
            self.counts[i] += 1
        self.total += 1
        self.sum += v

    def cumulative(self) -> List[Tuple[float, int]]:
        """(upper_bound, cumulative_count) pairs, +Inf last."""
        out, acc = [], 0
        for b, c in zip(self.buckets, self.counts):
            acc += c
            out.append((b, acc))
        out.append((float("inf"), self.total))
        return out


class StatGroup(dict):
    """A registry-owned counter group that IS a plain dict.

    The serving stack mutates its stats with ``stats[k] += v`` from hot
    loops and the test suite reads them as dicts; subclassing ``dict``
    keeps both contracts byte-for-byte while letting the registry
    enumerate and export the group.  No methods are overridden — there
    is deliberately nothing to slow down."""
    __slots__ = ()


def _fmt(v: float) -> str:
    """Prometheus sample value: integers render bare, floats via repr."""
    f = float(v)
    return str(int(f)) if f.is_integer() and abs(f) < 1e15 else repr(f)


def _labels(d: Mapping[str, Any]) -> str:
    inner = ",".join(f'{k}="{v}"' for k, v in d.items())
    return "{" + inner + "}" if inner else ""


class MetricsRegistry:
    """Single home for serving metrics: counter groups, gauges, labeled
    families and histograms, with Prometheus text exposition.

    Groups/families are attached *by reference* — the registry never
    copies or wraps the hot-path dicts, it only knows where they live —
    so registration has zero steady-state cost.  Names must be unique
    across all kinds (one exposition namespace).
    """

    def __init__(self, namespace: str = "sage"):
        self.namespace = namespace
        self._groups: "OrderedDict[str, Mapping[str, float]]" = \
            OrderedDict()
        self._gauges: "OrderedDict[str, Callable[[], float]]" = \
            OrderedDict()
        # flat families: name -> (mapping, label key); nested families:
        # prefix -> (mapping-of-dicts, label key)
        self._families: "OrderedDict[str, Tuple[Mapping, str]]" = \
            OrderedDict()
        self._nested: "OrderedDict[str, Tuple[Mapping, str]]" = \
            OrderedDict()
        self._hists: "OrderedDict[str, Histogram]" = OrderedDict()
        self._collectors: List[Callable[[], Iterable]] = []

    # -- registration ---------------------------------------------------
    def _claim(self, name: str) -> None:
        for pool in (self._groups, self._gauges, self._families,
                     self._nested, self._hists):
            if name in pool:
                raise ValueError(
                    f"metric name {name!r} already registered — one "
                    f"registry serves one scheduler/cache/fault set")

    def group(self, prefix: str,
              initial: Mapping[str, float]) -> StatGroup:
        """Create and register a counter group; returns the live
        :class:`StatGroup` the owner mutates directly."""
        sg = StatGroup(initial)
        self.attach_group(prefix, sg)
        return sg

    def attach_group(self, prefix: str,
                     mapping: Mapping[str, float]) -> None:
        """Adopt an existing stats dict (e.g. ``TrunkCache.stats``) as a
        counter group — the registry becomes its export surface without
        the owner changing a line of accounting code."""
        self._claim(prefix)
        self._groups[prefix] = mapping

    def gauge(self, name: str, fn: Callable[[], float]) -> None:
        """Register a point-in-time reading (resolved at export)."""
        self._claim(name)
        self._gauges[name] = fn

    def attach_family(self, name: str, mapping: Mapping[str, float],
                      label: str) -> None:
        """Adopt a flat ``{label_value: count}`` dict as one labeled
        counter family (e.g. ``FaultPlan.injected`` by fault kind)."""
        self._claim(name)
        self._families[name] = (mapping, label)

    def attach_nested(self, prefix: str,
                      mapping: Mapping[str, Mapping[str, float]],
                      label: str) -> None:
        """Adopt a ``{label_value: {key: count}}`` dict-of-dicts (e.g.
        the per-QoS class_stats mirrors) as per-key labeled families."""
        self._claim(prefix)
        self._nested[prefix] = (mapping, label)

    def histogram(self, name: str,
                  buckets: Sequence[float]) -> Histogram:
        self._claim(name)
        h = Histogram(buckets)
        self._hists[name] = h
        return h

    def collector(self, fn: Callable[[], Iterable]) -> None:
        """Register an export-time sample source: ``fn()`` yields
        ``(name, labels_dict, value, type)`` tuples (the hook the
        kernel-dispatch log uses to ride the same .prom file)."""
        self._collectors.append(fn)

    # -- views ----------------------------------------------------------
    def snapshot(self) -> Dict[str, float]:
        """Flat ``{qualified_name: value}`` view of everything (labels
        rendered into the key) — the test-facing export."""
        out: Dict[str, float] = {}
        for prefix, m in self._groups.items():
            for k, v in m.items():
                out[f"{prefix}_{k}"] = v
        for name, fn in self._gauges.items():
            out[name] = fn()
        for name, (m, label) in self._families.items():
            for k, v in m.items():
                out[f'{name}{{{label}="{k}"}}'] = v
        for prefix, (m, label) in self._nested.items():
            for lv, sub in m.items():
                for k, v in sub.items():
                    out[f'{prefix}_{k}{{{label}="{lv}"}}'] = v
        for name, h in self._hists.items():
            out[f"{name}_count"] = h.total
            out[f"{name}_sum"] = h.sum
        for fn in self._collectors:
            for name, labels, v, _kind in fn():
                out[f"{name}{_labels(labels or {})}"] = v
        return out

    def to_prometheus(self) -> str:
        """Text exposition format (one namespace, counters suffixed
        ``_total``, histograms with cumulative ``_bucket`` series)."""
        ns, lines = self.namespace, []

        def emit(name: str, kind: str, samples) -> None:
            base = f"{ns}_{name}" + ("_total" if kind == "counter"
                                     else "")
            lines.append(f"# TYPE {base} {kind}")
            for labels, v in samples:
                lines.append(f"{base}{_labels(labels)} {_fmt(v)}")

        for prefix, m in self._groups.items():
            for k, v in m.items():
                emit(f"{prefix}_{k}", "counter", [({}, v)])
        for name, fn in self._gauges.items():
            emit(name, "gauge", [({}, fn())])
        for name, (m, label) in self._families.items():
            emit(name, "counter",
                 [({label: k}, v) for k, v in m.items()])
        for prefix, (m, label) in self._nested.items():
            keys = sorted({k for sub in m.values() for k in sub})
            for k in keys:
                emit(f"{prefix}_{k}", "counter",
                     [({label: lv}, sub.get(k, 0))
                      for lv, sub in m.items()])
        for name, h in self._hists.items():
            base = f"{ns}_{name}"
            lines.append(f"# TYPE {base} histogram")
            for bound, acc in h.cumulative():
                le = "+Inf" if bound == float("inf") else _fmt(bound)
                lines.append(f'{base}_bucket{{le="{le}"}} {acc}')
            lines.append(f"{base}_sum {_fmt(h.sum)}")
            lines.append(f"{base}_count {h.total}")
        for fn in self._collectors:
            for name, labels, v, kind in fn():
                emit(name, kind, [(labels or {}, v)])
        return "\n".join(lines) + "\n"

    def export(self, path: str) -> int:
        """Write the Prometheus exposition; returns the line count."""
        text = self.to_prometheus()
        with open(path, "w") as f:
            f.write(text)
        return text.count("\n")


# -- tracing ------------------------------------------------------------

# process lanes in the exported trace: requests get tid=rid, groups
# tid=gid, exec is the single tick/launch timeline
PID_REQUESTS, PID_GROUPS, PID_EXEC = 1, 2, 3
_PROCESS_NAMES = {PID_REQUESTS: "requests", PID_GROUPS: "groups",
                  PID_EXEC: "exec"}


@dataclass
class TraceEvent:
    """One trace event in clock units (seconds; unscaled)."""
    name: str
    cat: str
    ph: str                       # "X" complete span | "i" instant
    ts: float
    dur: float
    pid: int
    tid: int
    args: Optional[Dict[str, Any]] = None


@dataclass
class Tracer:
    """Collects lifecycle and exec spans; exports Chrome trace-event JSON.

    Two clocks.  Request- and group-lane events (``instant`` / ``span``)
    carry the scheduler's ``now`` as given.  Exec-lane spans (``begin`` /
    ``end`` and the tick frame built on them) read ``clock`` at both
    ends, and while open each is also a ``jax.profiler.TraceAnnotation``
    named ``sage.<name>``, so a profile shows the host's tick phases
    beside the device's ops.  A caller that drives ``now`` from
    ``clock`` (the default ``time.perf_counter``) gets one timeline;
    tests pass a fake ``clock`` for a deterministic trace.

    Every event names its cause: lifecycle events sit on the lane of
    their request (tid=rid) or group (tid=gid), exec events carry the
    ``tick`` they ran in (absent outside ``tick()``, e.g. in ``submit``).

    ``time_scale`` maps clock units to microseconds at export (seconds
    -> us by default).  ``max_events`` bounds memory on long-lived
    servers: past it events are dropped (counted in ``dropped``) while
    ``counts()`` stays exact.
    """
    clock: Callable[[], float] = time.perf_counter
    time_scale: float = 1e6
    max_events: int = 1 << 20
    events: List[TraceEvent] = field(default_factory=list)
    dropped: int = 0

    def __post_init__(self):
        self._counts: Counter = Counter()
        # open exec spans, innermost last: (name, cat, start, annotation,
        # args)
        self._open: List[Tuple[str, str, float, Any, Dict[str, Any]]] = []
        self._tick: Optional[int] = None
        self._phase = False

    # -- core emit -------------------------------------------------------
    def _emit(self, name: str, cat: str, ph: str, ts: float, dur: float,
              pid: int, tid: int,
              args: Optional[Dict[str, Any]]) -> None:
        self._counts[name] += 1
        if len(self.events) < self.max_events:
            self.events.append(
                TraceEvent(name, cat, ph, ts, dur, pid, tid, args))
        else:
            self.dropped += 1

    def instant(self, name: str, ts: float, *, pid: int, tid: int,
                cat: str = "lifecycle", **args: Any) -> None:
        """A zero-duration lifecycle mark at an explicit scheduler-clock
        timestamp (request/group lanes)."""
        self._emit(name, cat, "i", ts, 0.0, pid, tid, args or None)

    def span(self, name: str, ts: float, dur: float, *, pid: int,
             tid: int, cat: str = "lifecycle", **args: Any) -> None:
        """A duration span at explicit scheduler-clock bounds."""
        self._emit(name, cat, "X", ts, dur, pid, tid, args or None)

    # -- exec lane --------------------------------------------------------
    def begin(self, name: str, cat: str = "exec", **args: Any) -> None:
        """Open an exec-lane span (and its ``sage.<name>`` annotation);
        spans nest, and ``end`` closes the innermost."""
        ann = TraceAnnotation(f"sage.{name}")
        ann.__enter__()
        self._open.append((name, cat, self.clock(), ann, args))

    def end(self, **args: Any) -> None:
        """Close the innermost open exec span; ``args`` join those given
        to ``begin``."""
        name, cat, t0, ann, a = self._open.pop()
        t1 = self.clock()
        ann.__exit__(None, None, None)
        a.update(args)
        if self._tick is not None:
            a["tick"] = self._tick
        self._emit(name, cat, "X", t0, t1 - t0, PID_EXEC, 0, a)

    def exec_mark(self, name: str, **args: Any) -> None:
        """Instant on the exec lane, now."""
        if self._tick is not None:
            args["tick"] = self._tick
        self._emit(name, "exec", "i", self.clock(), 0.0, PID_EXEC, 0,
                   args or None)

    def tick_begin(self, tick: int) -> None:
        """Open tick ``tick``'s frame: the ``tick`` span, parent of every
        exec event until ``tick_end``."""
        self._tick = tick
        self.begin("tick", cat="tick")

    def phase_begin(self, name: str) -> None:
        """Open a tick phase (closing any still-open one first, so the
        scheduler's admit -> launch -> advance -> complete sections each
        call only ``phase_begin``)."""
        self.phase_end()
        self.begin(f"tick.{name}", cat="tick")
        self._phase = True

    def phase_end(self) -> None:
        """Close the open tick phase, if any."""
        if self._phase:
            self._phase = False
            self.end()

    def tick_end(self, **args: Any) -> None:
        """Close the open phase and the tick frame."""
        self.phase_end()
        self.end(**args)
        self._tick = None

    # -- views & export --------------------------------------------------
    def counts(self) -> Dict[str, int]:
        """Exact per-name event counts (unaffected by ``max_events``
        drops) — what the reconciliation tests compare to ``stats``."""
        return dict(self._counts)

    def to_chrome(self) -> Dict[str, Any]:
        """Chrome trace-event JSON object (ts/dur in microseconds)."""
        sc = self.time_scale
        evs: List[Dict[str, Any]] = []
        for pid, pname in _PROCESS_NAMES.items():
            evs.append({"name": "process_name", "ph": "M", "pid": pid,
                        "tid": 0, "args": {"name": pname}})
        for e in self.events:
            d: Dict[str, Any] = {"name": e.name, "cat": e.cat,
                                 "ph": e.ph, "ts": e.ts * sc,
                                 "pid": e.pid, "tid": e.tid}
            if e.ph == "X":
                d["dur"] = e.dur * sc
            else:
                d["s"] = "t"       # instant scope: thread
            if e.args:
                d["args"] = e.args
            evs.append(d)
        return {"traceEvents": evs, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def export(self, path: str) -> int:
        """Write Perfetto-loadable JSON; returns the event count."""
        obj = self.to_chrome()
        with open(path, "w") as f:
            json.dump(obj, f)
        return len(obj["traceEvents"])
