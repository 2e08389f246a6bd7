"""Serving telemetry: tracer schema + reconciliation, exec spans on an
injected clock, metrics registry, dispatch attribution, safe_ratio, and
the zero-perturbation contract.

The expensive scenario (an overloaded, faulted, cached streaming run with
telemetry enabled) runs ONCE at module scope; the schema, conservation,
reconciliation and export tests all read that single run.  The
bitwise-identity test drives the same short trace twice — tracer and
registry on vs. off — and pins byte-equal latents and identical
summaries, the observability layer's core contract.
"""
import json

import jax
import numpy as np
import pytest

from repro.config import SageConfig, get_config
from repro.kernels import dispatch
from repro.launch.costs import chip_peaks, predict_drain, roofline_seconds
from repro.models import dit
from repro.models import text_encoder as te
from repro.serving import reports
from repro.serving.engine import SageServingEngine
from repro.serving.faults import FaultPlan
from repro.serving.telemetry import (Histogram, MetricsRegistry, Tracer,
                                     safe_ratio)
from repro.serving.trunk_cache import TrunkCache

CFG = get_config("sage-dit", smoke=True)
PARAMS = dit.init_params(CFG, jax.random.PRNGKey(0))
TC = te.text_cfg(dim=CFG.cond_dim, layers=2)
TEXT_PARAMS = te.init_text(jax.random.PRNGKey(1), TC)


def _engine(**kw):
    sage = SageConfig(total_steps=6, share_ratio=0.33, guidance_scale=3.0,
                      tau_min=0.3)
    return SageServingEngine(CFG, sage, dit_params=PARAMS,
                             text_params=TEXT_PARAMS, text_cfg=TC,
                             group_size=4, **kw)


def _themed_prompts(n, themes=3, seed=0):
    base = [f"a {c} circle on a white canvas"
            for c in ("red", "green", "blue", "yellow")][:themes]
    rng = np.random.RandomState(seed)
    return [base[rng.randint(themes)] for _ in range(n)]


# ---------------------------------------------------------------------------
# the shared chaos run (overload + faults + cache + QoS, telemetry on)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chaos_run():
    tracer = Tracer()
    metrics = MetricsRegistry()
    cache = TrunkCache(tau_trunk=0.9)
    faults = FaultPlan.parse("launch=0.2,miss=0.1,stall=0.1,seed=7")
    sched = _engine().streaming_scheduler(
        slice_steps=3, max_wait_ticks=1, trunk_cache=cache,
        max_groups_per_tick=2, admission="shed", faults=faults,
        tracer=tracer, metrics=metrics)
    prompts = _themed_prompts(20)
    rng = np.random.RandomState(3)
    arrival = np.cumsum(rng.exponential(0.4, len(prompts)))
    done, now, i = [], 0.0, 0
    ticks = 0
    while (i < len(prompts) or sched.pending) and ticks < 200:
        now += 1.0
        ticks += 1
        batch = []
        while i < len(prompts) and arrival[i] <= now:
            batch.append(prompts[i])
            i += 1
        if batch:
            # half the arrivals carry tight deadlines (interactive)
            half = len(batch) // 2
            if batch[:half]:
                sched.submit(batch[:half], now=now, deadline=now + 6.0,
                             qos="interactive")
            if batch[half:]:
                sched.submit(batch[half:], now=now, qos="batch")
        done.extend(sched.tick(now=now))
    return sched, tracer, metrics, done


def test_trace_schema_well_formed(chaos_run):
    """Every exported event: known phase, lane, non-negative duration,
    instants carry a scope, spans a dur."""
    _, tracer, _, _ = chaos_run
    obj = tracer.to_chrome()
    assert obj["traceEvents"], "chaos run must produce events"
    for e in obj["traceEvents"]:
        assert e["ph"] in ("X", "i", "M")
        if e["ph"] == "M":
            continue
        assert e["pid"] in (1, 2, 3)
        assert isinstance(e["name"], str) and e["name"]
        assert e["ts"] >= 0.0
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
        else:
            assert e["s"] == "t"


def test_request_conservation(chaos_run):
    """Every submitted request is accounted for exactly once across the
    span set: completes + sheds + rejects + pending == submits."""
    sched, tracer, _, _ = chaos_run
    c = tracer.counts()
    assert c["request.submit"] == 20
    accounted = (c.get("request.complete", 0)
                 + c.get("request.shed", 0)
                 + c.get("request.shed_faulted", 0)
                 + c.get("request.rejected_expired", 0)
                 + sched.pending)
    assert accounted == c["request.submit"]


def test_spans_reconcile_with_summary(chaos_run):
    """Exact agreement between trace-side counts and the summary()
    ledger: launches, completions, sheds, cache hits per tier,
    preemptions (the ISSUE acceptance bar)."""
    sched, tracer, _, done = chaos_run
    c, s = tracer.counts(), sched.summary()
    assert (c.get("phase.shared", 0) + c.get("phase.branch", 0)
            == s["launches"])
    assert c.get("request.complete", 0) == s["completed"] == len(
        [d for d in done if d.status in ("ok", "degraded")])
    assert c.get("request.shed", 0) == s["shed"]
    assert c.get("request.shed_faulted", 0) == s["shed_faulted"]
    assert c.get("group.preempt", 0) == s["preemptions"]
    assert c.get("group.resume", 0) == s["resumes"]
    assert c.get("group.retry", 0) == s["retries"]
    assert c.get("launch.fault", 0) == s["launch_faults"]
    assert c.get("tick.stall", 0) == s["stalled_ticks"]
    assert c.get("tick", 0) == s["ticks"]
    # cache: exact/ann split and found-tier attribution
    cache_hits = c.get("cache.exact", 0) + c.get("cache.ann", 0)
    assert cache_hits == s["cache_hits"]
    assert c.get("cache.exact", 0) == s["cache_exact_hits"]
    tiers = {"hbm": 0, "host": 0}
    for e in tracer.events:
        if e.name in ("cache.exact", "cache.ann"):
            tiers[e.args["tier"]] += 1
    assert tiers["hbm"] == s["cache_hits_hbm"]
    assert tiers["host"] == s["cache_hits_host"]


def test_chrome_export_round_trips(chaos_run, tmp_path):
    _, tracer, _, _ = chaos_run
    path = tmp_path / "trace.json"
    n = tracer.export(str(path))
    obj = json.loads(path.read_text())
    assert len(obj["traceEvents"]) == n > 0
    assert obj["otherData"]["dropped_events"] == 0


def test_prometheus_export(chaos_run, tmp_path):
    sched, _, metrics, _ = chaos_run
    text = metrics.to_prometheus()
    s = sched.summary()
    assert f"sage_scheduler_launches_total {int(s['launches'])}" in text
    assert f"sage_scheduler_completed_total {int(s['completed'])}" in text
    assert f"sage_cache_hits_total {int(s['cache_hits'])}" in text
    assert 'sage_faults_injected_total{kind="launch_fail"}' in text
    assert 'sage_scheduler_class_completed_total{qos="interactive"}' in text
    assert 'sage_scheduler_latency_ticks_bucket{le="+Inf"} ' in text
    # gauges resolve at export time
    assert f"sage_scheduler_ticks {sched.ticks}" in text
    path = tmp_path / "m.prom"
    assert metrics.export(str(path)) == text.count("\n")
    # snapshot view mirrors the group counters
    snap = metrics.snapshot()
    assert snap["scheduler_launches"] == s["launches"]
    assert snap["cache_hits"] == s["cache_hits"]


def test_metrics_registry_claims_names_once():
    reg = MetricsRegistry()
    reg.group("scheduler", {"a": 0})
    with pytest.raises(ValueError, match="already registered"):
        reg.attach_group("scheduler", {})
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("scheduler", lambda: 0)


# ---------------------------------------------------------------------------
# zero-perturbation: telemetry on == telemetry off, bitwise
# ---------------------------------------------------------------------------

#: the spans a traced run of the tick loop emits where the host works
PROGRAM_SPANS = ("tick", "tick.admit", "tick.launch", "tick.advance",
                 "tick.complete", "submit.dispatch", "submit.wait",
                 "request.queue", "pack.build", "pack.scatter",
                 "phase.shared", "phase.branch", "complete.fetch")


class FakeClock:
    """A clock that advances one unit per read and counts its reads."""

    def __init__(self):
        self.reads = 0

    def __call__(self) -> float:
        self.reads += 1
        return float(self.reads)


def _short_run(telemetry, packed=True, tracer=None):
    if telemetry and tracer is None:
        tracer = Tracer()
    metrics = MetricsRegistry() if telemetry else None
    sched = _engine().streaming_scheduler(
        slice_steps=3, max_wait_ticks=1, trunk_cache=TrunkCache(
            tau_trunk=0.9), tracer=tracer if telemetry else None,
        metrics=metrics, packed=packed)
    prompts = _themed_prompts(8, seed=5)
    done, now = [], 0.0
    sched.submit(prompts[:4], now=now)
    for _ in range(20):
        now += 1.0
        if now == 3.0:
            sched.submit(prompts[4:], now=now)
        done.extend(sched.tick(now=now))
        if not sched.pending and now > 3.0:
            break
    return sched.summary(), sorted(done, key=lambda c: c.prompt)


def _assert_invisible(packed):
    s_off, done_off = _short_run(telemetry=False, packed=packed)
    tracer = Tracer()
    s_on, done_on = _short_run(telemetry=True, packed=packed, tracer=tracer)
    assert len(done_off) == len(done_on) == 8
    for a, b in zip(done_off, done_on):
        assert a.prompt == b.prompt
        np.testing.assert_array_equal(a.image, b.image)
        assert a.nfe_share == b.nfe_share
    assert s_off == s_on
    return tracer


def test_telemetry_is_bitwise_invisible():
    """Identical latents, NFE and summary with tracing+registry on vs.
    off, every exec span included: the layer observes the tick loop, it
    never perturbs it."""
    counts = _assert_invisible(packed=True).counts()
    missing = [n for n in PROGRAM_SPANS if not counts.get(n)]
    assert not missing, f"the traced run emitted no {missing}"


def test_telemetry_is_bitwise_invisible_per_group():
    """The same contract on the one-launch-per-group path, whose
    dispatches run inside their ``phase.*`` spans too."""
    counts = _assert_invisible(packed=False).counts()
    s = counts.get("phase.shared", 0) + counts.get("phase.branch", 0)
    assert s > 0 and "pack.build" not in counts


def test_tracer_off_reads_no_clock(monkeypatch):
    """``tracer=None``: no emit site reads a clock or builds a profiler
    annotation (the scheduler is driven with explicit ``now``)."""
    import types

    from repro.serving import scheduler as scheduler_mod
    from repro.serving import telemetry
    calls = []

    def counted(name):
        return lambda *a, **k: calls.append(name) or 0.0

    fake_time = types.SimpleNamespace(
        perf_counter=counted("perf_counter"), monotonic=counted("monotonic"),
        time=counted("time"))
    monkeypatch.setattr(scheduler_mod, "time", fake_time)
    monkeypatch.setattr(telemetry, "TraceAnnotation", counted("annotation"))
    _, done = _short_run(telemetry=False)
    assert len(done) == 8 and calls == []


def test_exec_spans_on_an_injected_clock():
    """Exec spans read the tracer's clock twice each (once per instant),
    nest tick -> phase -> pack/dispatch in clock order, and name the tick
    that caused them; lifecycle events keep the scheduler's ``now``."""
    clock = FakeClock()
    tracer = Tracer(clock=clock)
    _short_run(telemetry=True, tracer=tracer)
    spans = [e for e in tracer.events if e.pid == 3 and e.ph == "X"]
    marks = [e for e in tracer.events if e.pid == 3 and e.ph == "i"]
    assert clock.reads == 2 * len(spans) + len(marks) > 0
    assert all(e.dur > 0 and e.ts == int(e.ts) for e in spans)
    ticks = {e.args["tick"]: e for e in spans if e.name == "tick"}
    for e in spans:
        if e.name.startswith("submit."):
            assert "tick" not in e.args and e.args["n"] == 4
            continue
        t = ticks[e.args["tick"]]
        assert t.ts <= e.ts and e.ts + e.dur <= t.ts + t.dur, e
    # within a tick the phases run in order, each pack build before its
    # dispatch and each scatter after it
    for tick in ticks:
        names = [e.name for e in sorted(spans, key=lambda e: e.ts)
                 if e.args.get("tick") == tick and e.name != "tick"]
        phases = [n for n in names if n.startswith("tick.")]
        assert phases == ["tick.admit", "tick.launch", "tick.advance",
                          "tick.complete"]
        inner = [n for n in names if not n.startswith("tick.")]
        for i, n in enumerate(inner):
            if n.startswith("phase."):
                assert inner[i - 1] == "pack.build"
                assert inner[i + 1] == "pack.scatter"
    lifecycle = [e for e in tracer.events if e.pid != 3]
    assert {e.ts for e in lifecycle} <= {float(t) for t in range(21)}


def test_request_queue_spans_join_arrival_to_launch():
    """One ``request.queue`` span per launched member, on its request's
    lane, from its arrival to its group's ``group.launch``."""
    tracer = Tracer(clock=FakeClock())
    _short_run(telemetry=True, tracer=tracer)
    launch = {e.tid: e for e in tracer.events if e.name == "group.launch"}
    submit = {e.tid: e for e in tracer.events if e.name == "request.submit"}
    queue = [e for e in tracer.events if e.name == "request.queue"]
    assert len(queue) == sum(e.args["n"] for e in launch.values()) == 8
    for e in queue:
        assert e.pid == 1 and e.ts == submit[e.tid].ts
        assert e.ts + e.dur == launch[e.args["gid"]].ts


# ---------------------------------------------------------------------------
# safe_ratio + zero-run summary defaults (satellite)
# ---------------------------------------------------------------------------

def test_safe_ratio():
    assert safe_ratio(6, 3) == 2.0
    assert safe_ratio(1, 0) == 0.0
    assert safe_ratio(0, 0) == 0.0
    assert safe_ratio(1, 0, default=1.0) == 1.0
    assert safe_ratio(3, 2) == 1.5


def test_zero_run_summary_reports_zero_ratios():
    """A scheduler that never ticked: every derived rate is exactly 0.0
    (one convention, no mixed sentinels)."""
    sched = _engine().streaming_scheduler(
        slice_steps=3, trunk_cache=TrunkCache(tau_trunk=0.9))
    s = sched.summary()
    for k in ("launches_per_tick", "pad_waste", "nfe_per_request",
              "cost_saving", "goodput_per_tick", "cache_hit_rate"):
        assert s[k] == 0.0, (k, s[k])
    assert sched.trunk_cache.hit_rate == 0.0


def test_histogram_buckets():
    h = Histogram([1, 2, 4])
    for v in (0.5, 1.0, 3.0, 100.0):
        h.observe(v)
    assert h.total == 4 and h.sum == 104.5
    assert h.cumulative() == [(1.0, 2), (2.0, 2), (4.0, 3),
                              (float("inf"), 4)]
    with pytest.raises(ValueError):
        Histogram([2, 1])


def test_tracer_max_events_cap_keeps_counts_exact():
    tr = Tracer(max_events=3)
    for i in range(10):
        tr.instant("x", float(i), pid=1, tid=0)
    assert len(tr.events) == 3 and tr.dropped == 7
    assert tr.counts()["x"] == 10
    assert tr.to_chrome()["otherData"]["dropped_events"] == 7


# ---------------------------------------------------------------------------
# kernel dispatch attribution
# ---------------------------------------------------------------------------

@pytest.fixture()
def dispatch_log():
    log = dispatch.DISPATCH_LOG
    was, log.enabled = log.enabled, True
    log.reset()
    yield log
    log.enabled = was
    log.reset()


def test_dispatch_records_fallbacks(dispatch_log):
    """The two known uncovered flash shapes — head_dim > 256 and a
    non-causal window — must show up as nonzero chunked fallbacks (the
    ISSUE acceptance bar), and a covered shape as a pallas route."""
    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (1, 8, 2, 512))   # head_dim > 256
    dispatch.attention(q, q, q, impl="pallas", causal=True,
                       interpret="on")
    q2 = jax.random.normal(k, (1, 8, 2, 32))
    dispatch.attention(q2, q2, q2, impl="pallas", window=4, causal=False,
                       interpret="on")        # non-causal window
    dispatch.attention(q2, q2, q2, impl="pallas", causal=True,
                       interpret="on")        # covered -> pallas
    fb = dispatch_log.fallbacks()
    reasons = {r["reason"] for r in fb}
    assert reasons == {"head_dim>256", "noncausal_window"}
    assert sum(r["count"] for r in fb) == 2
    routed = [r for r in dispatch_log.snapshot()
              if r["chosen"] == "pallas"]
    assert routed and all(r["reason"] == "requested" for r in routed)
    rep = reports.dispatch_report(dispatch_log)
    assert rep["fallback_launches"] == 2 and rep["enabled"]
    samples = list(dispatch_log.prometheus_samples())
    assert any(s[1]["reason"] == "head_dim>256" for s in samples)


def test_dispatch_log_disabled_records_nothing():
    log = dispatch.DispatchLog()
    assert not log.enabled and log.snapshot() == []
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 1, 16))
    dispatch.attention(q, q, q, impl="naive")   # global log disabled
    assert dispatch.DISPATCH_LOG.routes == {} or True  # no crash path


# ---------------------------------------------------------------------------
# reports (SLO + capacity)
# ---------------------------------------------------------------------------

def test_reports_join_and_render(chaos_run):
    sched, tracer, _, _ = chaos_run
    s = sched.summary()
    slo = reports.slo_report(s, counts=tracer.counts(),
                             pending=sched.pending)
    assert slo["conservation"]["residual"] == 0
    assert slo["overall"]["requests"] == 20
    assert set(slo["classes"]) == {"interactive", "batch"}
    assert slo["cache"]["hits"] == s["cache_hits"]
    cap = reports.capacity_report(
        s, total_steps=6, share_ratio=0.33, group_size=4, slice_steps=3,
        max_groups_per_tick=2, n_params=CFG.n_params(),
        n_tokens=(CFG.latent_size // CFG.patch) ** 2,
        device_kind="TPU v5 lite")
    assert cap["predicted"]["ticks_to_drain"] > 0
    assert cap["observed"]["ticks"] == s["ticks"]
    assert (cap["gaps"]["extra_ticks"]
            == s["ticks"] - cap["predicted"]["ticks_to_drain"])
    assert cap["roofline"]["seconds_per_request_floor"] >= 0.0
    text = reports.format_report(slo, cap, reports.dispatch_report())
    assert "== SLO report ==" in text and "ticks_to_drain" in text
    cols = reports.attributed_columns(s)
    assert "goodput=" in cols and "pad_waste=" in cols
    assert "cache_hit_rate=" in cols


def test_predict_drain_tick_economics():
    p = predict_drain(24, 4, 8, 2, 4)
    assert p.groups == 6
    assert p.shared_segments == 1 and p.branch_segments == 2
    assert p.ticks == 3                      # uncapped: packs advance
    assert p.nfe == 6 * 2 + 24 * 6
    assert p.nfe_independent == 24 * 8
    capped = predict_drain(24, 4, 8, 2, 4, max_groups_per_tick=2)
    assert capped.ticks == 9                 # 3 waves of 2 groups
    empty = predict_drain(0, 4, 8, 2, 4)
    assert empty.ticks == 0 and empty.nfe == 0


def test_chip_peaks_unknown_kind_raises():
    """The peak table never assumes a chip: an unknown ``device_kind``
    (the CPU backend's included) raises instead of defaulting to v5e."""
    v5e = chip_peaks("TPU v5 lite")
    assert v5e.flops == 197e12 and v5e.hbm_bw == 819e9
    assert roofline_seconds(v5e, 197e12) == 1.0
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(KeyError, match="no peak table entry"):
            chip_peaks(kind)
