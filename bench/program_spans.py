"""The program's own spans, placed on the profiler's clock.

A traced run hands the per-layer readers the program's ``Tracer``
(``ctx["tracer"]``) beside the reduced profile (``ctx["trace"]``).  The
tracer stamps its exec spans with ``time.perf_counter`` and stamps
request-lane spans with the scheduler's ``now``, which the harness also
reads from ``time.perf_counter``; the profile's times are the profiler's
own.  The two clocks are joined at one point: the harness enters the
``bench.window`` annotation (profiler clock, ``trace.window[0]``) and
reads ``perf_counter`` right after (``ctx["window"][0]``).  Device idle
inside a span is measured as ``Reduced.idle_in`` measures it for the
benchmark's own spans, against ``trace.busy``.

A tracer without a ``clock`` (one that lays its exec spans on a virtual
slot cursor) has no span whose times mean anything here: every reader
then finds nothing and returns ``None``.
"""
from __future__ import annotations

import bisect
from typing import Any, Dict, List, Optional, Sequence, Tuple

from bench.trace_reduce import overlap

Span = Tuple[float, float, Dict[str, Any]]


def shift(ctx) -> Optional[float]:
    """Profiler clock minus the program's clock, or ``None`` where the
    program's spans cannot be placed."""
    tracer, opened = ctx.get("tracer"), ctx["window"][0]
    if tracer is None or not hasattr(tracer, "clock") or opened is None:
        return None
    return ctx["trace"].window[0] - opened


def spans(ctx, name: str) -> List[Span]:
    """(start, end, args) of every ``name`` span the tracer holds, on the
    profiler's clock, whole."""
    d = shift(ctx)
    if d is None:
        return []
    return [(e.ts + d, e.ts + e.dur + d, e.args or {})
            for e in ctx["tracer"].events if e.name == name and e.ph == "X"]


def in_window(ctx, name: str) -> List[Span]:
    """The ``name`` spans that overlap the traced window, clipped to it
    (as the profile's own spans are)."""
    w0, w1 = ctx["trace"].window
    return [(max(a, w0), min(b, w1), args) for a, b, args in spans(ctx, name)
            if b > w0 and a < w1]


def seconds(found: Sequence[Span]) -> float:
    return sum(b - a for a, b, _ in found)


def idle_ms_per_tick(ctx, phases: Sequence[str]) -> Optional[float]:
    """Device-idle milliseconds inside the tick phases ``phases``
    (``tick.<phase>`` spans), over the ticks of the window."""
    ticks = in_window(ctx, "tick")
    if not ticks:
        return None
    busy = ctx["trace"].busy
    idle = sum((b - a) - overlap(busy, a, b) for p in phases
               for a, b, _ in in_window(ctx, f"tick.{p}"))
    return 1e3 * idle / len(ticks)


def ms_per_prompt(ctx, name: str) -> Optional[float]:
    """Milliseconds of ``name`` spans (``submit.*``, each with its prompt
    count ``n``) per prompt, over the window."""
    found = in_window(ctx, name)
    n = sum(args["n"] for _, _, args in found)
    if n == 0:
        return None
    return 1e3 * seconds(found) / n


def annotation_skew(ctx, profile) -> List[float]:
    """Seconds between each ``sage.<name>`` annotation's start in
    ``profile`` (the traced window's ``jax.profiler.ProfileData``) and the
    converted start of the nearest ``name`` span of the tracer: how well
    the one-point join lines the two clocks up."""
    starts: Dict[str, List[float]] = {}
    out = []
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith("sage."):
                    continue
                name = ev.name[len("sage."):]
                if name not in starts:
                    starts[name] = sorted(a for a, _, _ in spans(ctx, name))
                s, t = starts[name], ev.start_ns * 1e-9
                i = bisect.bisect_left(s, t)
                near = [abs(t - s[j]) for j in (i - 1, i) if 0 <= j < len(s)]
                if near:
                    out.append(min(near))
    return out
