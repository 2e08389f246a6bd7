"""The program's spans on the profiler's clock (``bench/program_spans.py``)
and the per-layer metrics that read them: the one-point clock join
against the profiler's own record of the ``sage.*`` annotations, each
reader on a hand-built window, and a tiny traced run of the harness."""
import time
import types

import pytest

from bench import harness, program_spans
from bench.tests import tiny
from bench.trace_reduce import Reduced
from repro.serving.telemetry import PID_REQUESTS, Tracer

READERS = ("text.dispatch_ms_per_request", "text.wait_ms_per_request",
           "sched.queue_wait_p50_s", "pack.host_ms_per_launch",
           "tick.idle_ms_admit_launch", "tick.idle_ms_advance",
           "tick.idle_ms_complete")


def read(name, ctx):
    return harness.load_module("metrics", name).read(ctx)


def test_clock_join_matches_the_profiler(tmp_path):
    """A ``bench.window`` annotation, then tracer spans: each span's start,
    moved onto the profiler's clock by the window's anchor, falls within
    100 us of the ``sage.*`` annotation the profiler recorded for it."""
    import jax
    from jax.profiler import ProfileData

    from bench.trace_reduce import reduce_file
    tracer = Tracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        opened = time.perf_counter()
        for tick in range(1, 4):
            tracer.tick_begin(tick)
            for phase in ("admit", "launch", "advance", "complete"):
                tracer.phase_begin(phase)
                tracer.begin("pack.build", phase="branch")
                time.sleep(0.002)
                tracer.end()
            tracer.tick_end()
            time.sleep(0.005)
    closed = time.perf_counter()
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    ctx = {"trace": reduce_file(path), "tracer": tracer,
           "window": (opened, closed)}
    skew = program_spans.annotation_skew(ctx, ProfileData.from_file(str(path)))
    assert len(skew) == len(tracer.events) == 3 * (1 + 4 + 4)
    skew.sort()
    assert skew[len(skew) // 2] <= 100e-6, skew


class SetClock:
    """A clock that reads whatever time the test last set."""
    t = 0.0

    def __call__(self):
        return self.t


def window_ctx():
    """A 10 s window, at 100 s on the profiler's clock and at 0 s on the
    program's, with the device busy over [100.5, 101] and [102, 104].

    The program: two submits (2 prompts, then 1), two ticks (the first
    with a branch launch, the second with a shared launch and the device
    idle), three requests launched in the window and two outside it."""
    clk = SetClock()
    tr = Tracer(clock=clk)

    def at(t, fn, *a, **kw):
        clk.t = t
        fn(*a, **kw)

    at(-1.0, tr.begin, "submit.dispatch", n=4)        # before the window
    at(-0.5, tr.end)
    at(0.1, tr.begin, "submit.dispatch", n=2)
    at(0.3, tr.end)
    at(0.3, tr.begin, "submit.wait", n=2)
    at(0.35, tr.end)
    at(1.0, tr.tick_begin, 1)
    at(1.0, tr.phase_begin, "admit")
    at(1.5, tr.phase_begin, "launch")
    at(2.5, tr.phase_begin, "advance")
    at(2.5, tr.begin, "pack.build", phase="branch")
    at(2.6, tr.end)
    at(2.6, tr.begin, "phase.branch")
    at(2.7, tr.end, rows=4, pad_rows=2, n_steps=4, groups=1)
    at(2.7, tr.begin, "pack.scatter", phase="branch")
    at(2.9, tr.end)
    at(4.2, tr.phase_begin, "complete")
    at(4.5, tr.tick_end)
    at(5.0, tr.begin, "submit.dispatch", n=1)
    at(5.1, tr.end)
    at(5.1, tr.begin, "submit.wait", n=1)
    at(5.25, tr.end)
    at(6.0, tr.tick_begin, 2)
    at(6.0, tr.phase_begin, "admit")
    at(6.25, tr.phase_begin, "launch")
    at(6.5, tr.phase_begin, "advance")
    at(6.5, tr.begin, "pack.build", phase="shared")
    at(6.55, tr.end)
    at(6.55, tr.begin, "phase.shared")
    at(6.6, tr.end, rows=1, pad_rows=0, n_steps=4, groups=1)
    at(6.6, tr.begin, "pack.scatter", phase="shared")
    at(6.7, tr.end)
    at(6.75, tr.phase_begin, "complete")
    at(7.0, tr.tick_end)
    for rid, (arrived, launched) in enumerate(
            [(0.1, 1.5), (0.3, 1.5), (5.0, 6.25),     # launched inside
             (-3.0, -0.5), (9.0, 10.5)]):             # and outside
        tr.span("request.queue", arrived, launched - arrived,
                pid=PID_REQUESTS, tid=rid, gid=rid)
    trace = Reduced((100.0, 110.0), 1, 2.5, {}, {}, {}, [],
                    busy=[(100.5, 101.0), (102.0, 104.0)])
    return {"trace": trace, "tracer": tr, "window": (0.0, 10.0)}


#: what each reader should find in ``window_ctx``
EXPECTED = {
    # (0.2 + 0.1) s of dispatch and (0.05 + 0.15) s of wait over 3 prompts
    "text.dispatch_ms_per_request": 100.0,
    "text.wait_ms_per_request": 200.0 / 3,
    # 1.4, 1.2 and 1.25 s from arrival to launch
    "sched.queue_wait_p50_s": 1.25,
    # (0.1 + 0.2) + (0.05 + 0.1) s of packing over 2 launches
    "pack.host_ms_per_launch": 225.0,
    # tick 1: admit idle 0.5 s, launch 0.5 (busy from 102), advance 0.2
    # (busy to 104), complete 0.3; tick 2: idle throughout
    "tick.idle_ms_admit_launch": (1.0 + 0.5) / 2 * 1e3,
    "tick.idle_ms_advance": (0.2 + 0.25) / 2 * 1e3,
    "tick.idle_ms_complete": (0.3 + 0.25) / 2 * 1e3,
}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_known_window(name):
    assert read(name, window_ctx()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_a_real_clock(name):
    """A tracer whose exec spans sit on a virtual slot cursor (no
    ``clock``): the same events read as nothing, not as durations."""
    ctx = window_ctx()
    ctx["tracer"] = types.SimpleNamespace(events=ctx["tracer"].events)
    assert read(name, ctx) is None


def test_tiny_traced_run_reads_every_new_metric():
    """A whole traced run at a tiny size on the CPU: every new metric is
    read, and the splits stay inside the benchmark's outside spans."""
    # the closed loop submits only when requests complete: a lead-in and a
    # window long enough that some complete inside it on a loaded CPU
    cell = dict(tiny.cell(), lead_in_s=4.0)
    r = harness.run("sage-dit-100m.themed-batch", tiny.SEED, 16.0, True,
                    spec=tiny.spec(), cell=cell, require_chip=False,
                    compile_cache=False, log=lambda *a: None)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    missing = [n for n in READERS if n not in m]
    assert not missing, (missing, m)
    assert all(m[n] > 0 for n in READERS), m
    # a submit cut by the window's edge can count in one span set and not
    # in the other, which at a dozen submits moves either split by up to
    # a tenth: only the lower bound holds at this size
    text = m["text.dispatch_ms_per_request"] + m["text.wait_ms_per_request"]
    assert 0.8 * m["host.submit_ms_per_request"] <= text
    tick = sum(m[n] for n in READERS if n.startswith("tick."))
    assert 0.8 * m["host.idle_ms_per_tick"] <= tick \
        <= m["host.idle_ms_per_tick"] + 1.0
