"""Continuous-batching request scheduler with cross-batch trunk reuse.

``SageServingEngine.step()`` shares work only *within* one synchronous
batch: drain the queue, group once, run every group to completion.  A
production engine sees requests arrive *over time*, so this module runs
the serving loop as repeated **ticks** over in-flight groups:

* **admission** — arriving requests join an *open* group via
  ``grouping.incremental_assign`` (edge to every member, the same clique
  invariant as batch grouping) or seed a new one; WHEN an open group
  launches is delegated to a pluggable ``serving.policies.LaunchPolicy``
  — ``"eager"`` (default oracle: full / ``max_wait_ticks`` / deadline
  pressure) or ``"pad_aware"`` (holds sub-full groups inside a
  deadline-safe window and fills existing pack buckets before opening new
  ones, trading a bounded launch delay for less pad waste and fewer
  launches per tick);
* **advance** — every in-flight group moves ``slice_steps`` sampler steps
  per tick through the resumable segment API
  (``core.shared_sampling.shared_phase`` / ``branch_phase`` over an
  explicit ``SampleCarry``), jit-bucketed by (phase, segment length,
  shapes) — the start position is traced, so slices at different grid
  offsets share one compilation.  By default ticks run **packed**
  (``packed=True``): groups sharing a pack signature (phase, sampler,
  beta bucket, shape, segment length — see ``serving.packing``) are
  gathered into ONE padded super-batch and advanced by a single phase
  call with per-row step/fork indices, collapsing G per-group launches
  into one per bucket; ``packed=False`` keeps the per-group launches (the
  conformance oracle).  Packing is bitwise-invisible to results; the cost
  is pad waste on partially-filled branch rows, reported by
  ``summary()['pad_waste']`` next to ``launches_per_tick``;
* **trunk reuse** — a completed shared phase is stored in a
  :class:`~repro.serving.trunk_cache.TrunkCache`; a newly launched group
  whose centroid hits the cache skips its shared phase entirely and forks
  straight into branching (SAGE's within-batch sharing, extended across
  batches — the diffusion analogue of ``shared_prefill``'s prefix cache);
* **completion** — finished groups decode and emit
  :class:`Completed` records carrying latency and NFE accounting;
  ``summary()`` reports p50/p95 latency, NFE per request, batch occupancy
  and queue depth.

Overload resilience (the regime where arrival rate exceeds service
rate) is layered on the same tick loop:

* **QoS classes** — every request carries ``qos`` (``interactive`` |
  ``batch``); grouping never mixes classes, the advance order is the
  pluggable ``launch_order`` comparator (default ``(qos, deadline)``),
  and when ``max_groups_per_tick`` caps the tick, slots are split by
  weighted-fair queueing over the classes (``qos_weights``, deficit
  round-robin);
* **preemption** — segments are resumable, so pausing a batch group is
  free: a deadline-at-risk group claims an advance slot outright and the
  displaced batch groups simply do not advance that tick (counted in
  ``stats['preemptions']``/``'resumes'``); a ``starvation_ticks`` bound
  forces any group skipped that many consecutive ticks into the next
  tick's slots, so batch can never starve;
* **admission control / load shedding** — each arrival passes a
  ``serving.policies.AdmissionPolicy`` fed a saturation estimate
  (backlog drain ticks + arrival-rate EWMA); past saturation requests
  are shed (``status="shed"``) or degraded to draft NFE (the group runs
  at the maximum share bucket, ``status="degraded"``), and a request
  whose deadline is already unmeetable is rejected up front
  (``status="rejected_expired"``) instead of churning the launch path;
* **fault tolerance** — an optional ``serving.faults.FaultPlan`` injects
  launch failures / cache corruption / tick stalls; failed segment
  launches retry with exponential backoff (the carry is untouched, so a
  successful retry is bitwise-identical to the fault-free run) and
  exhausting ``max_retries`` sheds the group with its NFE moved to the
  ``nfe_wasted`` ledger — every fault is recovered or accounted, never a
  silent drop.

With faults off, preemption off (or no capacity cap) and a single QoS
class, all of this reduces to the PR-5 tick loop exactly — the
conformance goldens are byte-stable against it.

Heterogeneous workloads (multi-resolution / quality tiers / mixed
samplers) ride the same tick loop — each axis is per-REQUEST at
``submit()`` and per-GROUP everywhere downstream:

* **shape** — ``submit(shape=(H, W, C))`` picks any patch-divisible
  latent geometry up to the trained grid (aspect buckets included);
  groups never mix shapes, so a hetero tick launches one stacked call
  per shape bucket with per-bucket pads, and the trunk cache/telemetry
  key on the group's own shape (``summary()`` reports per-shape launch
  and pad ledgers);
* **tier** — ``submit(tier=...)`` maps to a total step budget via the
  ``tiers`` table (draft/standard/premium by default).  The budget is
  per-row DATA, not a pack axis: rows gather timesteps from their own
  group's DDIM grid (``packing.pack_grid``), so draft and premium
  groups co-pack whenever segment lengths line up.  Overload
  ``degrade`` admission is a tier downgrade onto this mechanism
  (``degrade_tier``), NOT a forced beta compartment — degraded groups
  share launches with clean traffic;
* **sampler** — ``submit(sampler=...)`` picks ddim/dpmpp per request;
  groups never mix solvers, and with ``mix_samplers=True`` packs do:
  rows dispatch per-solver inside one stacked launch
  (``shared_sampling`` row dispatch; the PackKey sampler axis collapses
  to ``"*"``).

All of it stays bitwise-invisible: the ``packed=False`` per-group loop
remains the oracle for ANY hetero mix, and a homogeneous workload runs
the exact pre-hetero graph (1-D grid, scalar sampler, full-square
positional table).

The synchronous engine is literally a special case: :meth:`run_batch`
drains one prompt list through greedy-clique grouping and phase-aligned
packed segments (ONE stacked launch per phase per tick across all beta
buckets, no arrivals, no cache), which is what
``SageServingEngine.step()`` now delegates to.

Time is injectable: every ``submit``/``tick`` takes ``now`` (any
monotonically non-decreasing float — wall seconds, or virtual tick counts
for arrival-trace simulation as in ``examples/serve_shared.py
--streaming``); it defaults to ``time.monotonic()``.

Observability (``serving.telemetry``): the stats dicts are
:class:`~repro.serving.telemetry.StatGroup` members of a
:class:`~repro.serving.telemetry.MetricsRegistry` (``summary()`` is a
view over registry-owned state; pass ``metrics=`` to share a registry
with the export path), and an optional
:class:`~repro.serving.telemetry.Tracer` receives lifecycle spans for
every request/group transition (stamped with the injectable ``now``)
plus exec spans of the host's work — tick phases, pack build/scatter,
segment dispatch, text-tower dispatch/wait, completion fetch — stamped
by the tracer's own clock and mirrored as ``sage.*`` profiler
annotations.  With ``tracer=None`` (default) every emit site is a
single ``is not None`` branch and runs are bitwise-identical to the
pre-telemetry scheduler — tracing never touches RNG or sampler inputs,
so even an *enabled* tracer is output-invisible.
"""
from __future__ import annotations

import functools
import time
from collections import deque
from dataclasses import dataclass
from dataclasses import replace as dc_replace
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, SageConfig
from repro.core import grouping
from repro.core.schedule import Schedule, make_schedule
from repro.core.shared_sampling import (SampleCarry, branch_phase,
                                        branch_phase_nfe, fork_carry,
                                        group_mean, init_carry, phase_split,
                                        shared_phase, shared_phase_nfe)
from repro.models import dit, vae as vae_lib
from repro.models import text_encoder as te
from repro.serving import packing
from repro.serving.faults import FaultPlan
from repro.serving.policies import (DEGRADE, DEFAULT_QOS, DEFAULT_TIER,
                                    QOS_RANK, SHED,
                                    AdmissionContext, AdmissionPolicy,
                                    LaunchContext, LaunchPolicy,
                                    make_admission_policy, make_launch_order,
                                    make_launch_policy)
from repro.serving.telemetry import (LATENCY_BUCKETS, OCCUPANCY_BUCKETS,
                                     PID_GROUPS, PID_REQUESTS,
                                     QUEUE_DEPTH_BUCKETS, MetricsRegistry,
                                     Tracer, safe_ratio)
from repro.serving.trunk_cache import TrunkCache, TrunkEntry


@dataclass
class Completed:
    prompt: str
    image: Optional[np.ndarray]   # None when the request was not served
    group_id: int                 # -1 when refused before grouping
    nfe_share: float
    latency: float = 0.0          # completion time - arrival time
    cache_hit: bool = False       # trunk came from the cross-batch cache
    qos: str = DEFAULT_QOS
    tier: str = DEFAULT_TIER      # quality tier the request ran at
    status: str = "ok"            # ok | degraded | shed | rejected_expired


@dataclass
class Request:
    rid: int
    prompt: str
    t_arrival: float
    deadline: Optional[float]
    cond: np.ndarray              # (Lc, dc) projected text features
    pooled: np.ndarray            # (d,) pooled embedding (similarity space)
    qos: str = DEFAULT_QOS
    degraded: bool = False        # admitted at draft quality (overload)
    shape: Tuple[int, ...] = ()   # requested latent (H, W, C)
    tier: str = DEFAULT_TIER      # quality tier (total-step budget name)
    sampler: str = ""             # requested solver (ddim | dpmpp)


@dataclass
class _Group:
    """One in-flight (or open) group — always a (K=1, N) packing."""
    gid: int
    members: List[Request]
    created_tick: int
    state: str = "open"           # open | shared | branch | done
    beta: float = 0.0             # share-ratio bucket
    n_shared: int = 0
    steps_done: int = 0
    t_open: float = 0.0           # clock value when the group was seeded
    carry: Optional[SampleCarry] = None
    cbar: Any = None              # (1, Lc, dc)
    cond_flat: Any = None         # (N, Lc, dc)
    mask: Any = None              # (1, N)
    centroid: Optional[np.ndarray] = None
    cache_hit: bool = False
    nfe: float = 0.0
    t_launch: float = 0.0
    qos: str = DEFAULT_QOS        # members never mix classes
    degraded: bool = False        # any member admitted via tier downgrade
    shape: Tuple[int, ...] = ()   # latent (H, W, C) — members never mix
    tier: str = DEFAULT_TIER      # quality tier — members never mix
    sampler: str = "ddim"         # solver — members never mix
    total_steps: int = 0          # the tier's step budget (own DDIM grid)
    retries: int = 0              # consecutive failed segment launches
    next_try_tick: int = 0        # backoff gate: skip advance before this
    starved_ticks: int = 0        # consecutive ticks skipped by selection
    preempted: bool = False       # currently paused in favour of a
    #                               higher-class group (resume queue flag)

    def earliest_deadline(self) -> float:
        ds = [r.deadline for r in self.members if r.deadline is not None]
        return min(ds) if ds else float("inf")


class RequestScheduler:
    """Continuous-batching scheduler over the resumable sampling segments.

    Owns the full request path the synchronous engine used to inline:
    text-tower embedding, grouping (incremental for streaming, greedy
    cliques for :meth:`run_batch`), per-(phase, length) jitted segment
    runners, the trunk cache, VAE decode and the latency/NFE statistics.
    """

    def __init__(self, model_cfg: ModelConfig, sage: SageConfig,
                 dit_params, text_params, text_cfg, vae_params=None,
                 sched: Optional[Schedule] = None, group_size: int = 4,
                 group_max: Optional[int] = None,
                 branch_buckets: Sequence[float] = (0.2, 0.3, 0.4),
                 slice_steps: int = 4, max_wait_ticks: int = 2,
                 deadline_slack: float = 0.0,
                 trunk_cache: Optional[TrunkCache] = None,
                 max_groups_per_tick: Optional[int] = None,
                 packed: bool = True,
                 policy: Union[str, LaunchPolicy, None] = "eager",
                 launch_order: Any = "qos_edf",
                 qos_weights: Optional[Dict[str, int]] = None,
                 preempt: bool = True,
                 starvation_ticks: int = 4,
                 admission: Union[str, AdmissionPolicy, None] = None,
                 faults: Optional[FaultPlan] = None,
                 max_retries: int = 3,
                 tracer: Optional[Tracer] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 tiers: Optional[Dict[str, int]] = None,
                 degrade_tier: str = "draft",
                 mix_samplers: bool = False,
                 seed: int = 0):
        """``group_size`` is the packed width N (static sampler shape);
        ``group_max`` caps clique size during batch grouping and defaults
        to N — set it larger to let ``pad_groups`` split big cliques over
        multiple packed rows.  ``packed`` gathers pack-compatible
        in-flight groups into one denoiser launch per tick (see
        ``serving.packing``); ``packed=False`` advances each group with
        its own launch — same results bitwise, G× the launches.
        ``policy`` picks the launch policy (``serving.policies``):
        ``"eager"`` (default, the PR-4 oracle) launches a group the moment
        it is full / has waited ``max_wait_ticks`` / is deadline-urgent;
        ``"pad_aware"`` holds sub-full groups up to a deadline-safe window
        and fills existing pack buckets before opening new ones (a
        :class:`~repro.serving.policies.LaunchPolicy` instance also
        works, e.g. ``PadAwarePolicy(hold_ticks=4)``).

        Overload knobs: ``launch_order`` is the advance-priority
        comparator (``"fifo"`` / ``"edf"`` / ``"qos_edf"`` default, or a
        group -> key callable); ``qos_weights`` are the WFQ weights per
        class under a ``max_groups_per_tick`` cap (default interactive 2
        : batch 1); ``preempt`` lets deadline-at-risk groups claim slots
        from lower classes (``starvation_ticks`` bounds how long any
        group can be skipped); ``admission`` is the per-request overload
        policy (``"shed"`` / ``"degrade"`` /
        :class:`~repro.serving.policies.AdmissionPolicy`); ``faults`` is
        a :class:`~repro.serving.faults.FaultPlan` for chaos testing and
        ``max_retries`` bounds per-group launch retries before the
        shed escape hatch.

        Hetero knobs: ``tiers`` maps quality-tier names to total step
        budgets (default ``draft`` = T//2, ``standard`` = T,
        ``premium`` = T + T//2, with T = ``sage.total_steps``; a
        ``"standard"`` entry is always present — it is the ``submit``
        default and the ``run_batch`` tier); ``degrade_tier`` is the
        tier overload ``degrade`` admission downgrades requests to;
        ``mix_samplers=True`` lets packs mix ddim/dpmpp rows in one
        launch (default off: one launch per solver per tick).  Latent
        shape and sampler are per-request ``submit`` arguments.

        Observability: ``tracer`` receives lifecycle/phase spans
        (``None`` disables tracing at zero cost); ``metrics`` is the
        :class:`~repro.serving.telemetry.MetricsRegistry` the stats
        groups register into (one scheduler per registry; defaults to a
        private registry, so existing call sites see no change)."""
        if group_size < 1:
            raise ValueError(f"group_size must be >= 1, got {group_size}")
        if slice_steps < 1:
            raise ValueError(f"slice_steps must be >= 1, got {slice_steps}")
        self.cfg = model_cfg
        self.sage = sage
        self.sched = sched or make_schedule(1000)
        self.dit_params = dit_params
        self.text_params = text_params
        self.text_cfg = text_cfg
        self.vae_params = vae_params
        self.group_size = group_size
        self.group_max = group_size if group_max is None else group_max
        self.branch_buckets = tuple(branch_buckets)
        self.slice_steps = slice_steps
        self.max_wait_ticks = max_wait_ticks
        self.deadline_slack = deadline_slack
        self.trunk_cache = trunk_cache
        self.max_groups_per_tick = max_groups_per_tick
        self.packed = packed
        self.policy = make_launch_policy(policy)
        self.launch_order = make_launch_order(launch_order)
        self.qos_weights = dict(qos_weights or {"interactive": 2,
                                                "batch": 1})
        for q, w in self.qos_weights.items():
            if w <= 0:
                raise ValueError(
                    f"qos_weights[{q!r}] must be > 0, got {w}")
        self.preempt = preempt
        if starvation_ticks < 1:
            raise ValueError(
                f"starvation_ticks must be >= 1, got {starvation_ticks}")
        self.starvation_ticks = starvation_ticks
        self.admission = make_admission_policy(admission)
        T = sage.total_steps
        self.tiers: Dict[str, int] = (dict(tiers) if tiers is not None
                                      else {"draft": max(1, T // 2),
                                            "standard": T,
                                            "premium": T + max(1, T // 2)})
        self.tiers.setdefault("standard", T)
        for name, steps in self.tiers.items():
            if int(steps) < 1:
                raise ValueError(
                    f"tiers[{name!r}] must be >= 1 steps, got {steps}")
            self.tiers[name] = int(steps)
        if degrade_tier not in self.tiers:
            raise ValueError(f"degrade_tier {degrade_tier!r} not in tiers "
                             f"{sorted(self.tiers)}")
        self.degrade_tier = degrade_tier
        self.mix_samplers = bool(mix_samplers)
        self.faults = faults
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        self.max_retries = max_retries
        self.key = jax.random.PRNGKey(seed)
        # init noise is drawn per-gid from a fixed key, NOT from a key that
        # advances per launch: a group's trajectory then depends only on
        # its identity, never on launch order or timing — which is what
        # makes launch *policies* output-invariant for equal compositions
        self._launch_key = jax.random.fold_in(self.key, 0x5A9E)

        self.arrivals: List[Request] = []      # embedded, awaiting admission
        self.open_groups: List[_Group] = []
        self.inflight: List[_Group] = []
        self.ticks = 0
        self._next_rid = 0
        self._next_gid = 0
        self._runners: Dict[Tuple, Any] = {}

        # telemetry: the stats dicts live inside a MetricsRegistry as
        # StatGroup members (plain-dict semantics, so the += hot paths
        # and every stats-reading test are untouched); the registry is
        # the single export surface for scheduler + cache + fault
        # counters, gauges and histograms.  The tracer is optional and
        # fully inert when None.
        self.tracer = tracer
        self.metrics = metrics if metrics is not None else \
            MetricsRegistry()
        self.stats: Dict[str, float] = self.metrics.group("scheduler", {
            "nfe": 0.0, "nfe_independent": 0.0, "requests": 0,
            "completed": 0, "nfe_saved_cache": 0.0,
            # packed-execution accounting: segment launches, latent rows
            # those launches carried, and how many of the rows were pads
            "launches": 0, "pack_rows": 0, "pack_pad_rows": 0,
            # overload / robustness ledger: every refused or degraded
            # request and every injected-fault consequence is counted
            # here — conservation is requests == completed + shed +
            # shed_faulted + rejected_expired + pending
            "shed": 0, "degraded": 0, "rejected_expired": 0,
            "preemptions": 0, "resumes": 0, "retries": 0,
            "launch_faults": 0, "shed_faulted": 0, "stalled_ticks": 0,
            "deadline_met": 0, "deadline_missed": 0, "nfe_wasted": 0.0})
        # per-class mirrors of the request-outcome counters + latencies
        self.class_stats: Dict[str, Dict[str, float]] = {}
        self.class_latencies: Dict[str, "deque[float]"] = {}
        self.metrics.attach_nested("scheduler_class", self.class_stats,
                                   "qos")
        # per-tier NFE/outcome ledger and per-shape-bucket launch ledger
        # (the hetero observability: which step budget burned the NFE,
        # which geometry bucket owned the launches and the pad rows)
        self.tier_stats: Dict[str, Dict[str, float]] = {}
        self.metrics.attach_nested("scheduler_tier", self.tier_stats,
                                   "tier")
        self.shape_stats: Dict[str, Dict[str, float]] = {}
        self.metrics.attach_nested("scheduler_shape", self.shape_stats,
                                   "shape")
        self.metrics.gauge("scheduler_ticks", lambda: self.ticks)
        self.metrics.gauge("scheduler_pending", lambda: self.pending)
        self.metrics.gauge("scheduler_arrival_rate",
                           lambda: self._arrival_rate)
        self.metrics.gauge("scheduler_inflight_groups",
                           lambda: len(self.inflight))
        # compiled text-stack entries (process-wide): one per token shape
        # submitted, so a run of single-prompt submits reads 1
        self.metrics.gauge("text_tower_traces", te.text_tower_traces)
        if faults is not None:
            self.metrics.attach_family("faults_injected",
                                       faults.injected, "kind")
            self.metrics.attach_family("faults_queries",
                                       faults.queries, "kind")
        if trunk_cache is not None:
            self.metrics.attach_group("cache", trunk_cache.stats)
            self.metrics.gauge("cache_bytes", lambda: trunk_cache.bytes)
            self.metrics.gauge("cache_entries",
                               lambda: len(trunk_cache))
            self.metrics.gauge("cache_hbm_bytes",
                               lambda: trunk_cache.tier_bytes["hbm"])
            self.metrics.gauge("cache_host_bytes",
                               lambda: trunk_cache.tier_bytes["host"])
        # fixed-bucket histograms next to the exact-percentile deques:
        # the deques keep summary()'s percentiles exact over the trailing
        # window, the histograms give the exporter cumulative
        # distributions that never reset
        self._h_latency = self.metrics.histogram(
            "scheduler_latency_ticks", LATENCY_BUCKETS)
        self._h_queue = self.metrics.histogram(
            "scheduler_queue_depth", QUEUE_DEPTH_BUCKETS)
        self._h_occupancy = self.metrics.histogram(
            "scheduler_pack_occupancy", OCCUPANCY_BUCKETS)
        # arrival-process estimate: EWMA of submitted requests per tick
        # (feeds AdmissionContext.backlog decisions and the adaptive
        # pad-aware hold budget via LaunchContext.arrival_rate)
        self._arrival_rate = 0.0
        self._arrivals_since_tick = 0
        # clock value of the tick being executed — the timestamp source
        # for trace events emitted below tick()/run_batch() in the call
        # tree (e.g. fork/store marks inside _after_segment)
        self._tick_now = 0.0
        # deficit-round-robin credit per class (persists across ticks so
        # fractional weight ratios average out over time)
        self._wfq_credit: Dict[str, float] = {}
        # bounded windows: a long-lived server must not grow stat state
        # without bound; summary() percentiles are over the trailing window
        stat_window = self._stat_window = 65_536
        self.latencies: "deque[float]" = deque(maxlen=stat_window)
        self.occupancy: "deque[float]" = deque(maxlen=stat_window)
        #                                      members/group_size at launch
        self.queue_depth: "deque[int]" = deque(maxlen=stat_window)
        #                                      waiting requests per tick

    # -- embedding ------------------------------------------------------
    def _embed(self, prompts: Sequence[str]):
        tr = self.tracer
        if tr is not None:
            # host tokenize + dispatch of the text tower
            tr.begin("submit.dispatch", n=len(prompts))
        toks = te.tokenize(prompts, max_len=self.cfg.cond_len)
        feats, pooled = te.encode_text(self.text_params, self.text_cfg, toks)
        # project per-token features to the DiT cond width if needed
        if feats.shape[-1] != self.cfg.cond_dim:
            reps = -(-self.cfg.cond_dim // feats.shape[-1])
            feats = jnp.tile(feats, (1, 1, reps))[..., :self.cfg.cond_dim]
        if tr is not None:
            tr.end()
            tr.begin("submit.wait", n=len(prompts))   # device + copy
        out = np.asarray(feats), np.asarray(pooled)
        if tr is not None:
            tr.end()
        return out

    @property
    def _latent_shape(self) -> Tuple[int, int, int]:
        """The DEFAULT latent geometry (full square trained grid) — the
        shape a request gets when ``submit`` is not given one.  Every
        execution site keys on the GROUP's own ``g.shape``; this property
        only seeds defaults."""
        H = self.cfg.latent_size
        return (H, H, self.cfg.latent_channels)

    def _null_cond(self):
        return jnp.zeros((self.cfg.cond_len, self.cfg.cond_dim))

    def _cfg_key(self, g: "_Group"):
        """Everything (besides the centroid/beta/shape) that must match for
        a cached trunk to be reusable — per GROUP now: the group's own
        sampler and step budget ride the key, so a draft-tier or dpmpp
        trunk can never serve a premium/ddim group.  Params are not
        hashed: the cache lives inside one scheduler, whose params are
        fixed."""
        s, c = self.sage, self.cfg
        return (c.name, c.attn_impl, g.sampler, s.step_impl, g.total_steps,
                round(s.guidance_scale, 6), round(s.clip_x0, 6),
                s.shared_uncond_cfg, self.sched.T)

    # -- jit-bucketed segment runners -----------------------------------
    def _eps_fn(self, params):
        cfg = self.cfg
        return lambda z, t, c: dit.forward(params, cfg, z, t, c)

    def _runner_cfg(self, samplers):
        """Resolve a runner's sampler spec: a solver NAME (uniform pack —
        the scalar path, graph-identical to pre-hetero) or a per-row
        tuple (mixed pack — ``row_samplers`` dispatch)."""
        if isinstance(samplers, str):
            return dc_replace(self.sage, sampler=samplers), None
        return self.sage, tuple(samplers)

    def _shared_runner(self, n_steps: int, samplers):
        """Jitted shared-phase segment with the DiT params bound as a
        traced argument: a closure would embed them in the executable as
        constants (gigabytes at published widths)."""
        key = ("shared", n_steps, samplers)
        if key not in self._runners:
            sched = self.sched
            sage, rs = self._runner_cfg(samplers)

            @jax.jit
            def shared_segment(params, carry, cbar, null, grid):
                return shared_phase(self._eps_fn(params), sched, sage, carry,
                                    cbar, null, n_steps, grid=grid,
                                    row_samplers=rs)
            self._runners[key] = functools.partial(shared_segment,
                                                   self.dit_params)
        return self._runners[key]

    def _branch_runner(self, n_steps: int, samplers):
        """Jitted branch-phase segment; params bound as in
        :meth:`_shared_runner`."""
        key = ("branch", n_steps, samplers)
        if key not in self._runners:
            sched = self.sched
            sage, rs = self._runner_cfg(samplers)

            @jax.jit
            def branch_segment(params, carry, cond_flat, mask, null,
                               fork_idx, grid):
                return branch_phase(self._eps_fn(params), sched, sage, carry,
                                    cond_flat, mask, null, n_steps, fork_idx,
                                    grid=grid, row_samplers=rs)
            self._runners[key] = functools.partial(branch_segment,
                                                   self.dit_params)
        return self._runners[key]

    # -- submission & admission -----------------------------------------
    @staticmethod
    def _now(now: Optional[float]) -> float:
        return time.monotonic() if now is None else float(now)

    def _check_shape(self, shape) -> Tuple[int, int, int]:
        """Validate a requested latent geometry: 3-tuple, the model's
        channel count, patch-divisible spatial dims within the trained
        positional grid (the DiT windows its pos table down — it cannot
        extrapolate up)."""
        shp = tuple(int(x) for x in shape)
        if len(shp) != 3:
            raise ValueError(f"shape must be (H, W, C), got {shape!r}")
        H, W, C = shp
        if C != self.cfg.latent_channels:
            raise ValueError(f"shape channels {C} != model latent_channels "
                             f"{self.cfg.latent_channels}")
        p, top = self.cfg.patch, self.cfg.latent_size
        if H < 1 or W < 1 or H % p or W % p:
            raise ValueError(f"shape ({H},{W}) must be positive multiples "
                             f"of patch {p}")
        if H > top or W > top:
            raise ValueError(f"shape ({H},{W}) exceeds the trained grid "
                             f"{top}x{top}")
        return shp

    @staticmethod
    def _per_request(val, default, n: int, name: str) -> List:
        """Broadcast a scalar-for-batch submit argument or validate a
        per-prompt sequence of length n."""
        if val is None:
            return [default] * n
        if isinstance(val, str) or (isinstance(val, tuple)
                                    and val and not isinstance(val[0],
                                                               (tuple, list))):
            return [val] * n
        vals = list(val)
        if len(vals) != n:
            raise ValueError(f"{name} sequence length {len(vals)} != "
                             f"{n} prompts")
        return vals

    def submit(self, prompts: Sequence[str], now: Optional[float] = None,
               deadline: Optional[float] = None,
               qos: Union[str, Sequence[str]] = DEFAULT_QOS,
               shape=None, tier=None, sampler=None) -> List[int]:
        """Queue prompts (one text-tower call per submit batch); they are
        grouped at the next tick.  ``qos`` is one class for the whole
        batch or a per-prompt sequence (``"interactive"`` | ``"batch"``).
        ``shape`` / ``tier`` / ``sampler`` are the hetero axes — each one
        value for the whole batch or a per-prompt sequence: ``shape`` a
        patch-divisible (H, W, C) up to the trained grid (default the
        full square), ``tier`` a ``tiers`` name mapping to the total step
        budget (default ``"standard"``), ``sampler`` ``"ddim"`` |
        ``"dpmpp"`` (default ``sage.sampler``).  Requests only group with
        compartment-mates (same qos AND shape AND tier AND sampler).
        Returns request ids."""
        if not prompts:
            return []
        now = self._now(now)
        n = len(prompts)
        qs = self._per_request(qos, DEFAULT_QOS, n, "qos")
        for q in qs:
            if q not in QOS_RANK:
                raise ValueError(f"unknown qos class {q!r}; "
                                 f"have {sorted(QOS_RANK)}")
        shapes = [self._check_shape(s) for s in self._per_request(
            tuple(shape) if isinstance(shape, (tuple, list)) else shape,
            self._latent_shape, n, "shape")]
        tiers = self._per_request(tier, DEFAULT_TIER, n, "tier")
        for t in tiers:
            if t not in self.tiers:
                raise ValueError(f"unknown tier {t!r}; "
                                 f"have {sorted(self.tiers)}")
        samplers = self._per_request(sampler, self.sage.sampler, n,
                                     "sampler")
        for s in samplers:
            if s not in ("ddim", "dpmpp"):
                raise ValueError(f"unknown sampler {s!r}; "
                                 f"have ['ddim', 'dpmpp']")
        conds, pooled = self._embed(prompts)
        rids = []
        tr = self.tracer
        for p, c, e, q, shp, t, smp in zip(prompts, conds, pooled, qs,
                                           shapes, tiers, samplers):
            r = Request(self._next_rid, p, now, deadline, c, e, qos=q,
                        shape=shp, tier=t, sampler=smp)
            self._next_rid += 1
            self.arrivals.append(r)
            rids.append(r.rid)
            if tr is not None:
                tr.instant("request.submit", now, pid=PID_REQUESTS,
                           tid=r.rid, qos=q, deadline=deadline,
                           shape="x".join(map(str, shp)), tier=t,
                           sampler=smp)
        self.stats["requests"] += len(prompts)
        self._arrivals_since_tick += len(prompts)
        return rids

    # -- overload accounting ---------------------------------------------
    def _cstat(self, qos: str, key: str, inc: float = 1) -> None:
        d = self.class_stats.setdefault(
            qos, {"requests": 0, "completed": 0, "shed": 0, "degraded": 0,
                  "rejected_expired": 0, "preemptions": 0,
                  "deadline_met": 0, "deadline_missed": 0})
        d[key] = d.get(key, 0) + inc

    def _tstat(self, tier: str, key: str, inc: float = 1) -> None:
        d = self.tier_stats.setdefault(
            tier, {"requests": 0, "completed": 0, "nfe": 0.0})
        d[key] = d.get(key, 0) + inc

    def _refuse(self, r: Request, status: str,
                now: float = 0.0) -> Completed:
        """An accounted non-service outcome (shed / rejected_expired):
        the request leaves the system as a Completed record with no
        image — conservation still sees it exactly once."""
        self.stats[status] += 1
        self._cstat(r.qos, "requests")
        self._cstat(r.qos, status)
        self._tstat(r.tier, "requests")
        if self.tracer is not None:
            self.tracer.instant(f"request.{status}", now,
                                pid=PID_REQUESTS, tid=r.rid, qos=r.qos)
        return Completed(prompt=r.prompt, image=None, group_id=-1,
                         nfe_share=0.0, latency=0.0, qos=r.qos,
                         tier=r.tier, status=status)

    def _remaining_ticks(self, g: _Group) -> int:
        """Conservative advance-ticks left for an in-flight group: one
        segment per tick plus one for the shared->branch boundary (the
        group's own tier budget, not the deployment default)."""
        rem = g.total_steps - g.steps_done
        return -(-rem // self.slice_steps) + (1 if g.state == "shared"
                                              else 0)

    def _backlog_ticks(self) -> float:
        """Saturation estimate: ticks to drain the work already in the
        system.  Under a ``max_groups_per_tick`` cap the advance slots
        are the bottleneck (sum of per-group ticks over the cap);
        uncapped, every group advances each tick and the backlog is just
        the longest remaining group."""
        ttf = self._ticks_to_finish()
        loads = [self._remaining_ticks(g) for g in self.inflight]
        loads += [self._ticks_to_finish(g.total_steps)
                  for g in self.open_groups]
        if not loads:
            return 0.0
        if self.max_groups_per_tick is None:
            return float(max(loads))
        return sum(loads) / self.max_groups_per_tick

    def _admit(self, now: float) -> List[Completed]:
        """Admission: expired-deadline rejection and the overload policy
        first, then class-compartmented incremental grouping (a request
        only joins an open group of its own (qos, tier, shape, sampler)
        compartment — mixing qos would let a batch member drag an
        interactive group; mixing tiers/shapes/samplers inside a *group*
        is impossible because members share one trunk).  A DEGRADE
        verdict is a tier downgrade (to ``degrade_tier``): the request
        then groups — and packs — with native requests of that tier.
        Returns the refusal records for this tick."""
        notices: List[Completed] = []
        if not self.arrivals:
            return notices
        backlog = self._backlog_ticks()
        ttf = self._ticks_to_finish()
        per_group = (ttf / self.max_groups_per_tick
                     if self.max_groups_per_tick else 0.0)
        arrivals, self.arrivals = self.arrivals, []
        # member-embedding stacks maintained incrementally: only the group
        # an arrival joins changes, so a burst of A arrivals over G open
        # groups costs O(A + G) stacks, not O(A * G)
        open_embeds = [np.stack([m.pooled for m in g.members])
                       for g in self.open_groups]
        tr = self.tracer
        for r in arrivals:
            # bugfix (was: churn through the normal launch path): a
            # deadline already expired — or expiring within one segment,
            # so even an immediate solo launch cannot finish in time —
            # is refused up front with its own status
            if r.deadline is not None and r.deadline <= now + 1.0:
                notices.append(self._refuse(r, "rejected_expired", now))
                continue
            verdict = self.admission.decide(AdmissionContext(
                now=now, qos=r.qos, deadline=r.deadline,
                backlog_ticks=backlog, ticks_to_finish=ttf,
                arrival_rate=self._arrival_rate))
            if verdict == SHED:
                notices.append(self._refuse(r, "shed", now))
                continue
            if verdict == DEGRADE:
                r.degraded = True
                r.tier = self.degrade_tier
            self._cstat(r.qos, "requests")
            self._tstat(r.tier, "requests")
            if tr is not None:
                tr.instant("request.admit", now, pid=PID_REQUESTS,
                           tid=r.rid, qos=r.qos, degraded=r.degraded,
                           tier=r.tier)
            cand = [i for i, g in enumerate(self.open_groups)
                    if g.qos == r.qos and g.tier == r.tier
                    and g.shape == r.shape and g.sampler == r.sampler]
            gi = grouping.incremental_assign(
                r.pooled, [open_embeds[i] for i in cand],
                self.sage.tau_min, group_max=self.group_size)
            if gi >= 0:
                i = cand[gi]
                self.open_groups[i].members.append(r)
                self.open_groups[i].degraded = (
                    self.open_groups[i].degraded or r.degraded)
                open_embeds[i] = np.concatenate(
                    [open_embeds[i], r.pooled[None]], 0)
                gid, seeded = self.open_groups[i].gid, False
            else:
                self.open_groups.append(
                    _Group(self._next_gid, [r], created_tick=self.ticks,
                           t_open=now, qos=r.qos, degraded=r.degraded,
                           shape=r.shape, tier=r.tier, sampler=r.sampler,
                           total_steps=self.tiers[r.tier]))
                self._next_gid += 1
                open_embeds.append(np.asarray(r.pooled)[None])
                backlog += per_group     # each seeded group deepens the
                #                          queue the next verdict sees
                gid, seeded = self.open_groups[-1].gid, True
            if tr is not None:
                tr.instant("request.group", now, pid=PID_REQUESTS,
                           tid=r.rid, gid=gid, seeded=seeded)
        return notices

    # -- launch ----------------------------------------------------------
    @staticmethod
    def _min_sim(sim_sub: np.ndarray) -> float:
        """Group tightness = min pairwise similarity of a square sim
        submatrix; singletons pin to 1.0 (they share with nobody, so the
        bucket choice only affects their own — cost-neutral — split)."""
        if sim_sub.shape[0] == 1:
            return 1.0
        iu = np.triu_indices(sim_sub.shape[0], k=1)
        return float(sim_sub[iu].min())

    def _beta_bucket(self, min_sim: float, adaptive: bool) -> float:
        """THE share-ratio bucket rule (used by both the streaming launch
        path and ``run_batch`` — one copy, so the trunk-cache
        ``beta_bucket`` key can never diverge between them): tighter
        groups share more, min_sim in [0, 1] -> beta_raw in [0, 0.5],
        snapped to the nearest branch bucket."""
        if not adaptive:
            return self.sage.share_ratio
        beta_raw = float(np.clip(min_sim, 0.0, 1.0)) * 0.5
        return min(self.branch_buckets, key=lambda b: abs(b - beta_raw))

    def _group_beta(self, members: List[Request], adaptive: bool) -> float:
        """Per-group share-ratio bucket (singletons only drag *their own*
        bucket — the old batch-mean bug is gone)."""
        e = np.stack([m.pooled for m in members])
        return self._beta_bucket(
            self._min_sim(grouping.similarity_matrix(e)), adaptive)

    def _effective_beta(self, g: _Group, adaptive: bool) -> float:
        """The bucket a group actually runs at — the similarity rule,
        nothing else.  Degraded admission used to force the maximum
        share bucket here, which pushed degraded groups into their own
        pack compartment (distinct phase boundaries) even though beta is
        not a pack axis; the NFE saving now comes from the *tier* step
        budget instead, so degraded groups co-pack with native ones."""
        return self._group_beta(g.members, adaptive)

    def _launch(self, g: _Group, now: float, adaptive: bool,
                beta: Optional[float] = None) -> None:
        T = g.total_steps
        g.beta = self._effective_beta(g, adaptive) if beta is None \
            else beta
        g.n_shared, _ = phase_split(T, g.beta)
        N = len(g.members)
        cond = jnp.asarray(np.stack([m.cond for m in g.members]))
        g.cond_flat = cond                              # (N, Lc, dc)
        g.mask = jnp.ones((1, N))
        g.cbar = group_mean(cond[None], g.mask)         # (1, Lc, dc)
        g.centroid = np.mean(np.stack([m.pooled for m in g.members]), 0)
        g.t_launch = now
        self.occupancy.append(N / self.group_size)
        self._h_occupancy.observe(N / self.group_size)
        self.stats["nfe_independent"] += 2.0 * N * T
        tr = self.tracer
        if tr is not None:
            # hold span: the open-group dwell from seed to launch (what
            # a launch policy trades against pad waste)
            tr.span("group.hold", g.t_open, now - g.t_open,
                    pid=PID_GROUPS, tid=g.gid, qos=g.qos,
                    waited_ticks=self.ticks - g.created_tick)

        entry = None
        if self.trunk_cache is not None and g.n_shared > 0:
            cs = self.trunk_cache.stats
            pre = (cs["exact_hits"], cs["hits_host"])
            entry = self.trunk_cache.lookup(
                g.centroid, g.beta, self._cfg_key(g), g.shape,
                payload="trunk")
            if tr is not None:
                # classify the lookup from the cache's own counters
                # (exact-key vs ANN/similarity vs miss, and which tier
                # served it) — the cache API stays untouched
                if entry is None:
                    tr.instant("cache.miss", now, pid=PID_GROUPS,
                               tid=g.gid)
                else:
                    kind = ("cache.exact" if cs["exact_hits"] > pre[0]
                            else "cache.ann")
                    tier = ("host" if cs["hits_host"] > pre[1]
                            else "hbm")
                    tr.instant(kind, now, pid=PID_GROUPS, tid=g.gid,
                               tier=tier)
        if entry is not None:
            # cross-batch trunk hit: skip the shared phase entirely, fork
            # straight into branching from the cached branch-point latent.
            trunk = SampleCarry(jnp.asarray(entry.z),
                                jnp.zeros_like(jnp.asarray(entry.z)),
                                jnp.int32(entry.step_idx))
            g.carry = fork_carry(trunk, N)
            g.steps_done = g.n_shared
            g.state = "branch"
            g.cache_hit = True
            self.stats["nfe_saved_cache"] += shared_phase_nfe(1, g.n_shared)
        else:
            rng = jax.random.fold_in(self._launch_key, g.gid)
            g.carry = init_carry(rng, 1, g.shape)
            if g.n_shared == 0:
                g.carry = fork_carry(g.carry, N)
                g.state = "branch"
            else:
                g.state = "shared"
        if tr is not None:
            tr.instant("group.launch", now, pid=PID_GROUPS, tid=g.gid,
                       n=N, beta=g.beta, n_shared=g.n_shared, qos=g.qos,
                       cache_hit=g.cache_hit, state=g.state)
            for r in g.members:
                tr.span("request.queue", r.t_arrival, now - r.t_arrival,
                        pid=PID_REQUESTS, tid=r.rid, gid=g.gid)
        self.open_groups.remove(g)
        self.inflight.append(g)

    # -- advance ---------------------------------------------------------
    def _store_trunk(self, g: _Group) -> None:
        if self.trunk_cache is None:
            return
        stored = self.trunk_cache.insert(TrunkEntry(
            z=g.carry.z, eps_prev=g.carry.eps_prev, step_idx=g.n_shared,
            beta_bucket=g.beta, rng_fold=g.gid, centroid=g.centroid,
            cfg_key=self._cfg_key(g), payload="trunk"),
            shape=g.shape)
        if self.tracer is not None:
            self.tracer.instant("cache.store", self._tick_now,
                                pid=PID_GROUPS, tid=g.gid,
                                stored=bool(stored))

    def _dispatch(self, phase: str, runner, args: Tuple, rows: int,
                  pad_rows: int, n_steps: int, groups: int = 1,
                  shape=None):
        """THE segment-launch choke point: every denoiser dispatch —
        packed bucket or per-group — runs here exactly once, inside its
        ``phase.<phase>`` span, so the stats ledger and the trace's
        launch spans can never disagree (the reconciliation test pins
        spans == launches).  Returns the runner's output carry."""
        tr = self.tracer
        skey = "x".join(map(str, shape)) if shape else None
        if tr is not None:
            tr.begin(f"phase.{phase}")
        out = runner(*args)
        if tr is not None:
            kw = {"shape": skey} if skey is not None else {}
            tr.end(rows=rows, pad_rows=pad_rows, n_steps=n_steps,
                   groups=groups, **kw)
        self.stats["launches"] += 1
        self.stats["pack_rows"] += rows
        self.stats["pack_pad_rows"] += pad_rows
        if skey is not None:
            d = self.shape_stats.setdefault(
                skey, {"launches": 0, "rows": 0, "pad_rows": 0})
            d["launches"] += 1
            d["rows"] += rows
            d["pad_rows"] += pad_rows
        return out

    def _after_segment(self, g: _Group, s: int) -> None:
        """Post-advance accounting + phase transitions, shared by the
        packed and per-group paths (NFE counts the *logical* per-group
        evals — pad rows are real compute but ride the pad-waste stat,
        keeping NFE comparable between modes and with the sync engine)."""
        g.steps_done += s
        if g.state == "shared":
            g.nfe += shared_phase_nfe(1, s)
            if g.steps_done == g.n_shared:
                self._store_trunk(g)
                g.carry = fork_carry(g.carry, len(g.members))
                g.state = "branch"
                if self.tracer is not None:
                    self.tracer.instant("group.fork", self._tick_now,
                                        pid=PID_GROUPS, tid=g.gid,
                                        step_idx=g.n_shared)
        else:
            g.nfe += float(branch_phase_nfe(g.mask, s,
                                            self.sage.shared_uncond_cfg))
            if g.steps_done == g.total_steps:
                g.state = "done"

    def _advance(self, g: _Group) -> bool:
        """One segment of at most ``slice_steps`` for ONE group — the
        ``packed=False`` oracle path (one launch per group per tick).
        Returns whether the launch succeeded; an injected failure leaves
        the carry untouched (the retry re-runs the same computation)."""
        if self.faults is not None and self.faults.launch_fails():
            self.stats["launch_faults"] += 1
            return False
        null = self._null_cond()
        grid = packing.pack_grid([g], self.sched.T)
        if g.state == "shared":
            s = min(self.slice_steps, g.n_shared - g.steps_done)
            g.carry = self._dispatch(
                "shared", self._shared_runner(s, g.sampler),
                (g.carry, g.cbar, null, grid), 1, 0, n_steps=s,
                shape=g.shape)
        else:
            s = min(self.slice_steps, g.total_steps - g.steps_done)
            g.carry = self._dispatch(
                "branch", self._branch_runner(s, g.sampler),
                (g.carry, g.cond_flat, g.mask, null, jnp.int32(g.n_shared),
                 grid), len(g.members), 0, n_steps=s, shape=g.shape)
        self._after_segment(g, s)
        g.retries = 0
        return True

    def _advance_packed(self, todo: List[_Group],
                        slice_steps: Optional[int] = None,
                        align_phases: bool = False) -> List[_Group]:
        """One tick of packed execution: bucket the in-flight groups by
        pack signature, advance each bucket with ONE phase call over a
        stacked carry (per-row step/fork indices), scatter back.  Buckets
        are built from pre-tick states, so a group forking shared->branch
        this tick joins branch packs only from the next tick — exactly
        the per-group ordering.  Transitions (trunk-cache stores, forks,
        completions) run AFTER all buckets, in ``todo`` order, so the
        cache's insert/LRU-recency order is identical to per-group mode
        even when a byte budget forces evictions.

        ``align_phases=True`` (the ``run_batch`` drain) aligns segment
        lengths within each phase so every tick issues at most one
        stacked launch per phase — see ``packing.build_packs``.

        Returns the groups whose bucket's launch was failed by the fault
        plan this tick (their carries are untouched; ``tick()`` routes
        them through the retry/shed machinery).  Fault injection is per
        *launch*, so one failed bucket takes all its pack-mates down
        together — exactly the blast radius of a real failed dispatch."""
        null = self._null_cond()
        seg_len: Dict[int, int] = {}
        failed: List[_Group] = []
        tr = self.tracer
        for key, groups in packing.build_packs(
                todo, self.slice_steps if slice_steps is None else
                slice_steps, mix_samplers=self.mix_samplers,
                align_phases=align_phases, order_key=self.launch_order):
            s = key.n_steps
            if self.faults is not None and self.faults.launch_fails():
                self.stats["launch_faults"] += 1
                if tr is not None:
                    tr.exec_mark("launch.fault", phase=key.phase,
                                 groups=len(groups))
                failed.extend(groups)
                continue
            if tr is not None:
                tr.begin("pack.build", phase=key.phase)
            if key.phase == "shared":
                carry, cbar = packing.pack_shared(groups)
                rs = packing.pack_samplers(groups)
                grid = packing.pack_grid(groups, self.sched.T)
                runner = self._shared_runner(
                    s, rs if rs is not None else groups[0].sampler)
                args = (carry, cbar, null, grid)
                rows, pads = len(groups), 0
            else:
                carry, cond, mask, fork = packing.pack_branch(
                    groups, self.group_size)
                rs = packing.pack_samplers(groups, self.group_size)
                grid = packing.pack_grid(groups, self.sched.T,
                                         self.group_size)
                runner = self._branch_runner(
                    s, rs if rs is not None else groups[0].sampler)
                args = (carry, cond, mask, null, fork, grid)
                rows, pads = packing.pad_stats(groups, self.group_size)
            if tr is not None:
                tr.end()
            out = self._dispatch(key.phase, runner, args, rows, pads,
                                 n_steps=s, groups=len(groups),
                                 shape=key.shape)
            if tr is not None:
                tr.begin("pack.scatter", phase=key.phase)
            if key.phase == "shared":
                packing.unpack_shared(out, groups)
            else:
                packing.unpack_branch(out, groups, self.group_size)
            if tr is not None:
                tr.end()
            for g in groups:
                seg_len[g.gid] = s
        for g in todo:
            if g.gid in seg_len:
                self._after_segment(g, seg_len[g.gid])
                g.retries = 0
        return failed

    def _handle_failures(self, failed: List[_Group],
                         now: float) -> List[Completed]:
        """Retry-with-backoff, bounded by ``max_retries``: a failed group
        keeps its carry and is re-advanced after ``2^(retries-1)`` ticks
        (capped at 8) — a successful retry is bitwise-identical to the
        fault-free run.  Exhaustion takes the shed escape hatch: members
        complete with ``status='shed'`` and the NFE already spent moves
        to the ``nfe_wasted`` ledger (never a silent drop)."""
        out: List[Completed] = []
        tr = self.tracer
        for g in failed:
            g.retries += 1
            if g.retries <= self.max_retries:
                self.stats["retries"] += 1
                g.next_try_tick = self.ticks + min(2 ** (g.retries - 1), 8)
                if tr is not None:
                    tr.instant("group.retry", now, pid=PID_GROUPS,
                               tid=g.gid, attempt=g.retries,
                               next_try_tick=g.next_try_tick)
                continue
            self.inflight.remove(g)
            self.stats["shed_faulted"] += len(g.members)
            self.stats["nfe_wasted"] += g.nfe
            for r in g.members:
                self._cstat(r.qos, "shed")
                if tr is not None:
                    tr.instant("request.shed_faulted", now,
                               pid=PID_REQUESTS, tid=r.rid, gid=g.gid,
                               qos=r.qos)
                out.append(Completed(
                    prompt=r.prompt, image=None, group_id=g.gid,
                    nfe_share=0.0, latency=now - r.t_arrival, qos=r.qos,
                    tier=r.tier, status="shed"))
        return out

    def _decode(self, latents: jnp.ndarray) -> np.ndarray:
        """latents (B, H, W, C) -> images (or raw latents without a VAE)."""
        if self.vae_params is not None:
            return np.asarray(vae_lib.decode(self.vae_params, latents))
        return np.asarray(latents)

    def _complete(self, g: _Group, now: float,
                  record_latency: bool = True) -> List[Completed]:
        tr = self.tracer
        if tr is not None:
            tr.begin("complete.fetch", rows=len(g.members))
        imgs = self._decode(g.carry.z)
        if tr is not None:
            tr.end()
        self.stats["nfe"] += g.nfe
        self.stats["completed"] += len(g.members)
        done = []
        for i, r in enumerate(g.members):
            # per-REQUEST status: a degraded (tier-downgraded) request
            # may co-group with native draft-tier traffic, which stays
            # plain "ok" — degradation is an admission outcome, not a
            # property of the group it happened to land in
            status = "degraded" if r.degraded else "ok"
            lat = now - r.t_arrival if record_latency else 0.0
            if tr is not None:
                tr.span("request.complete", r.t_arrival, lat,
                        pid=PID_REQUESTS, tid=r.rid, gid=g.gid,
                        qos=r.qos, status=status, tier=r.tier,
                        cache_hit=g.cache_hit)
            if record_latency:
                self._h_latency.observe(lat)
                # per-class outcome ledger (goodput = deadline-met
                # completions; deadline-free requests always count as met)
                self.latencies.append(lat)
                self.class_latencies.setdefault(
                    r.qos, deque(maxlen=self._stat_window)).append(lat)
                self._cstat(r.qos, "completed")
                self._tstat(r.tier, "completed")
                self._tstat(r.tier, "nfe", g.nfe / len(g.members))
                if r.degraded:
                    self.stats["degraded"] += 1
                    self._cstat(r.qos, "degraded")
                met = r.deadline is None or now <= r.deadline
                key = "deadline_met" if met else "deadline_missed"
                self.stats[key] += 1
                self._cstat(r.qos, key)
            done.append(Completed(
                prompt=r.prompt, image=imgs[i], group_id=g.gid,
                nfe_share=g.nfe / len(g.members), latency=lat,
                cache_hit=g.cache_hit, qos=r.qos, tier=r.tier,
                status=status))
        return done

    # -- launch-policy context -------------------------------------------
    def _ticks_to_finish(self, total_steps: Optional[int] = None) -> int:
        """Conservative ticks a freshly launched group needs to complete:
        one segment per tick, plus one for the shared->branch boundary.
        ``total_steps`` defaults to the deployment (standard-tier) budget;
        pass a group's own tier budget for per-group estimates."""
        t = self.sage.total_steps if total_steps is None else total_steps
        return -(-t // self.slice_steps) + 1

    def _open_signature(self, g: _Group, adaptive: bool) -> packing.PackKey:
        """The pack bucket an OPEN group would occupy if launched this
        tick (``policies.LaunchContext.signature_of``)."""
        n_shared, _ = phase_split(g.total_steps,
                                  self._effective_beta(g, adaptive))
        limit = n_shared if n_shared > 0 else g.total_steps
        return packing.PackKey(
            "shared" if n_shared > 0 else "branch",
            packing.MIXED if self.mix_samplers else g.sampler,
            tuple(g.shape), min(self.slice_steps, limit))

    def _launch_context(self, now: float, adaptive: bool) -> LaunchContext:
        ttf = max([self._ticks_to_finish()]
                  + [self._ticks_to_finish(g.total_steps)
                     for g in self.open_groups])
        return LaunchContext(
            now=now, tick=self.ticks, group_size=self.group_size,
            max_wait_ticks=self.max_wait_ticks,
            deadline_slack=self.deadline_slack,
            ticks_to_finish=ttf,
            inflight_signatures=frozenset(
                packing.pack_signature(g, self.slice_steps,
                                       self.mix_samplers)
                for g in self.inflight),
            signature_of=lambda g: self._open_signature(g, adaptive),
            arrival_rate=self._arrival_rate)

    # -- advance-slot selection ------------------------------------------
    def _at_risk(self, g: _Group, now: float) -> bool:
        """Deadline-at-risk test: skipping even one tick (one time unit
        under the virtual clock) would push the group's conservative
        finish past its earliest deadline (plus the configured slack)."""
        dl = g.earliest_deadline()
        if dl == float("inf"):
            return False
        return dl - now <= (self._remaining_ticks(g)
                            + self.deadline_slack + 1.0)

    def _preemptive_select(self, ready: List[_Group], cap: int,
                           now: float) -> List[_Group]:
        """Claim the capped advance slots in three passes over the
        ``launch_order``-sorted ready list: any group at the
        ``starvation_ticks`` bound is forced in first (the bound is a
        hard guarantee — it must hold even when every tick brings fresh
        at-risk work, so it outranks the deadline pass), then
        deadline-at-risk groups take slots outright (this is the
        preemption — displaced groups simply do not advance, their
        carries parked until resumed), then the remaining slots go by
        deficit round-robin over the QoS classes with ``qos_weights``
        (credit persists across ticks, so fractional weight ratios are
        honoured in the long run)."""
        slots: List[_Group] = []
        taken = set()

        def take(g: _Group) -> None:
            slots.append(g)
            taken.add(g.gid)

        # pass 1: the no-starvation bound — longest-starved first (NOT
        # launch order: under deep backlog many groups sit at the bound,
        # and scanning by class would let starving interactive groups
        # shut out a longer-starved batch group indefinitely)
        starving = sorted(
            (g for g in ready
             if g.starved_ticks >= self.starvation_ticks),
            key=lambda g: (-g.starved_ticks,) + tuple(self.launch_order(g)))
        for g in starving:
            if len(slots) >= cap:
                break
            take(g)
        for g in ready:              # pass 2: deadline-at-risk claim
            if len(slots) >= cap:
                break
            if g.gid not in taken and self._at_risk(g, now):
                take(g)
        if len(slots) < cap:         # pass 3: weighted-fair round-robin
            queues: Dict[str, "deque[_Group]"] = {}
            for g in ready:
                if g.gid not in taken:
                    queues.setdefault(g.qos, deque()).append(g)
            classes = sorted(queues,
                             key=lambda q: (QOS_RANK.get(q, len(QOS_RANK)),
                                            q))
            while len(slots) < cap and any(queues.values()):
                for q in classes:
                    if not queues[q]:
                        self._wfq_credit[q] = 0.0   # no deficit hoarding
                        continue
                    self._wfq_credit[q] = (self._wfq_credit.get(q, 0.0)
                                           + self.qos_weights.get(q, 1))
                    while (queues[q] and len(slots) < cap
                           and self._wfq_credit[q] >= 1.0):
                        take(queues[q].popleft())
                        self._wfq_credit[q] -= 1.0
        # preemption accounting: anyone the plain priority prefix would
        # have advanced this tick but the claiming passes displaced
        for g in ready[:cap]:
            if g.gid not in taken and not g.preempted:
                g.preempted = True
                self.stats["preemptions"] += 1
                self._cstat(g.qos, "preemptions")
                if self.tracer is not None:
                    self.tracer.instant("group.preempt", now,
                                        pid=PID_GROUPS, tid=g.gid,
                                        qos=g.qos)
        return slots

    def _select_todo(self, now: float) -> List[_Group]:
        """This tick's advance set.  Uncapped, every launch-ready group
        advances (retry backoff is the only filter).  Under a
        ``max_groups_per_tick`` cap, ``preempt=False`` gives the slots to
        the plain ``launch_order`` prefix (the PR-5 rule under the
        default single-class order); ``preempt=True`` routes them through
        :meth:`_preemptive_select`.  Starvation/resume bookkeeping lives
        here so both paths age skipped groups consistently."""
        ready = [g for g in self.inflight if g.next_try_tick <= self.ticks]
        ready.sort(key=self.launch_order)
        cap = self.max_groups_per_tick
        if cap is None or len(ready) <= cap:
            selected = ready
        elif not self.preempt:
            selected = ready[:cap]
        else:
            selected = self._preemptive_select(ready, cap, now)
        chosen = {g.gid for g in selected}
        for g in ready:
            if g.gid in chosen:
                if g.preempted:
                    g.preempted = False
                    self.stats["resumes"] += 1
                    if self.tracer is not None:
                        self.tracer.instant("group.resume", now,
                                            pid=PID_GROUPS, tid=g.gid,
                                            qos=g.qos)
                g.starved_ticks = 0
            else:
                g.starved_ticks += 1
        return selected

    # -- the tick --------------------------------------------------------
    def tick(self, now: Optional[float] = None,
             adaptive: Optional[bool] = None) -> List[Completed]:
        """One engine iteration: admit arrivals (returning shed /
        rejected notices alongside completions), launch the groups the
        launch policy selects, advance the selected in-flight groups one
        segment each, emit completions."""
        now = self._now(now)
        adaptive = (self.sage.adaptive_branch if adaptive is None
                    else adaptive)
        self.ticks += 1
        self._tick_now = now
        tr = self.tracer
        if tr is not None:
            tr.tick_begin(self.ticks)
        # arrival-process EWMA (requests per tick) — feeds admission
        # decisions and the adaptive pad-aware hold budget
        self._arrival_rate = (0.5 * self._arrivals_since_tick
                              + 0.5 * self._arrival_rate)
        self._arrivals_since_tick = 0
        if self.faults is not None and self.faults.tick_stalls():
            # a stalled tick is pure lost time: no admission, no
            # launches, no segments.  Deadline machinery sees the lost
            # time on the next live tick — stalled-away slack surfaces
            # as at-risk claims or rejected_expired, never silently
            self.stats["stalled_ticks"] += 1
            if tr is not None:
                tr.exec_mark("tick.stall")
                tr.tick_end(stalled=True)
            return []
        if tr is not None:
            tr.phase_begin("admit")
        done: List[Completed] = self._admit(now)
        depth = sum(len(g.members) for g in self.open_groups)
        self.queue_depth.append(depth)
        self._h_queue.observe(depth)

        if tr is not None:
            tr.phase_begin("launch")
        ctx = self._launch_context(now, adaptive)
        for g in self.policy.launches(list(self.open_groups), ctx):
            self._launch(g, now, adaptive)

        if tr is not None:
            tr.phase_begin("advance")
        todo = self._select_todo(now)
        failed: List[_Group] = []
        if self.packed:
            if todo:
                failed = self._advance_packed(todo)
        else:
            for g in todo:
                if not self._advance(g):
                    failed.append(g)
        if tr is not None:
            tr.phase_begin("complete")
        done.extend(self._handle_failures(failed, now))
        for g in todo:
            if g.state == "done":
                done.extend(self._complete(g, now))
                self.inflight.remove(g)
        if tr is not None:
            tr.tick_end(completions=len(done))
        return done

    def drain(self, now: Optional[float] = None,
              max_ticks: int = 10_000) -> List[Completed]:
        """Tick until no work remains.  ``now`` is passed to every tick:
        provide it when driving a virtual clock (the clock then stands
        still for the whole drain); omit it only under the wall-clock
        default — mixing virtual-time submits with a wall-clock drain
        would corrupt the latency stats."""
        done: List[Completed] = []
        for _ in range(max_ticks):
            if not (self.arrivals or self.open_groups or self.inflight):
                break
            done.extend(self.tick(now))
        return done

    @property
    def pending(self) -> int:
        return (len(self.arrivals)
                + sum(len(g.members) for g in self.open_groups)
                + sum(len(g.members) for g in self.inflight))

    # -- synchronous special case ----------------------------------------
    def run_batch(self, prompts: Sequence[str],
                  adaptive: Optional[bool] = None) -> List[Completed]:
        """Drain one prompt list synchronously — the old engine semantics
        as a special case of the segment machinery: greedy-clique grouping
        over the whole batch, per-group beta buckets, no arrivals, no
        trunk cache.  ``SageServingEngine.step()`` delegates here.

        Execution routes through ``serving.packing`` with phase-aligned
        segments: every drain tick issues ONE stacked launch per phase
        across ALL beta buckets (beta is per-row data — ``step_idx`` /
        ``fork_idx`` — not a pack-compatibility axis), instead of the old
        one-shared-plus-one-branch launch *per bucket*.  NFE accounting is
        unchanged: pad rows ride the pad-waste ledger, never NFE."""
        if not prompts:
            return []
        now = self._now(None)
        self._tick_now = now
        adaptive = (self.sage.adaptive_branch if adaptive is None
                    else adaptive)
        conds, pooled = self._embed(prompts)
        sim = grouping.similarity_matrix(pooled)
        cliques = grouping.greedy_clique_groups(
            sim, self.sage.tau_min, group_max=self.group_max)
        self.stats["requests"] += len(prompts)

        # one _Group per packed row (a clique larger than N occupies
        # multiple rows in flatten_groups order); every row inherits its
        # clique's beta bucket — per-clique, not batch-mean (a singleton's
        # pinned 1.0 min-sim must not drag other cliques' buckets)
        batch: List[_Group] = []
        # sync drain: no cache, and no fault injection — the drain loop
        # has no tick cadence to retry on, and run_batch is the
        # conformance oracle the chaos tests compare *against*
        cache, self.trunk_cache = self.trunk_cache, None
        faults, self.faults = self.faults, None
        try:
            for clique in cliques:
                beta = self._beta_bucket(
                    self._min_sim(sim[np.ix_(clique, clique)]), adaptive)
                for row in grouping.flatten_groups([clique],
                                                   self.group_size):
                    members = []
                    for m in row:
                        members.append(Request(
                            self._next_rid, prompts[m], now, None,
                            conds[m], pooled[m],
                            shape=tuple(self._latent_shape),
                            tier="standard", sampler=self.sage.sampler))
                        self._next_rid += 1
                    g = _Group(self._next_gid, members,
                               created_tick=self.ticks,
                               shape=tuple(self._latent_shape),
                               tier="standard", sampler=self.sage.sampler,
                               total_steps=self.tiers["standard"])
                    self._next_gid += 1
                    self.open_groups.append(g)
                    self._launch(g, now, adaptive, beta=beta)
                    batch.append(g)

            done: List[Completed] = []
            live = list(batch)
            # NOTE: the drain deliberately does NOT advance self.ticks —
            # wait counters of any STREAMING open groups on this
            # scheduler are measured in ticks, and a sync drain must not
            # age them toward a padded force-launch
            while live:
                self._advance_packed(live,
                                     slice_steps=self.sage.total_steps,
                                     align_phases=True)
                for g in list(live):
                    if g.state == "done":
                        done.extend(self._complete(g, now,
                                                   record_latency=False))
                        live.remove(g)
                        self.inflight.remove(g)
        finally:
            self.trunk_cache = cache
            self.faults = faults
        return done

    # -- reporting -------------------------------------------------------
    @property
    def cost_saving(self) -> float:
        return 1.0 - safe_ratio(self.stats["nfe"],
                                self.stats["nfe_independent"],
                                default=1.0)

    def summary(self) -> Dict[str, float]:
        """End-of-run rollup.  This is a *view over the registry-homed
        counters* (``self.stats`` and friends live in
        ``self.metrics``); zero-denominator ratios uniformly report
        ``0.0`` via :func:`telemetry.safe_ratio`."""
        lat = np.asarray(self.latencies, np.float64)
        out = {
            "requests": self.stats["requests"],
            "completed": self.stats["completed"],
            "nfe": self.stats["nfe"],
            "nfe_independent": self.stats["nfe_independent"],
            "nfe_saved_cache": self.stats["nfe_saved_cache"],
            "nfe_per_request": safe_ratio(self.stats["nfe"],
                                          self.stats["completed"]),
            "cost_saving": self.cost_saving,
            "latency_p50": float(np.percentile(lat, 50)) if lat.size else 0.0,
            "latency_p95": float(np.percentile(lat, 95)) if lat.size else 0.0,
            "occupancy_mean": (float(np.mean(self.occupancy))
                               if self.occupancy else 0.0),
            "queue_depth_mean": (float(np.mean(self.queue_depth))
                                 if self.queue_depth else 0.0),
            "ticks": self.ticks,
            # packed-execution economics: launches_per_tick is the
            # dispatch pressure packing exists to collapse; pad_waste is
            # what it pays (fraction of launched latent rows that were
            # mask-0 padding)
            "launches": self.stats["launches"],
            "launches_per_tick": safe_ratio(self.stats["launches"],
                                            self.ticks),
            "pad_waste": safe_ratio(self.stats["pack_pad_rows"],
                                    self.stats["pack_rows"]),
        }
        # overload / robustness ledger + goodput (deadline-met
        # completions — the number a QoS policy is supposed to maximise
        # under saturation, where raw completion counts reward lateness)
        for k in ("shed", "shed_faulted", "degraded", "rejected_expired",
                  "preemptions", "resumes", "retries", "launch_faults",
                  "stalled_ticks", "deadline_met", "deadline_missed",
                  "nfe_wasted"):
            out[k] = self.stats[k]
        out["goodput"] = self.stats["deadline_met"]
        out["goodput_per_tick"] = safe_ratio(self.stats["deadline_met"],
                                             self.ticks)
        out["arrival_rate"] = self._arrival_rate
        out["backlog_ticks"] = self._backlog_ticks()
        for q, cs in sorted(self.class_stats.items()):
            for k, v in sorted(cs.items()):
                out[f"{q}_{k}"] = v
        for q, lats in sorted(self.class_latencies.items()):
            a = np.asarray(lats, np.float64)
            out[f"{q}_latency_p50"] = (float(np.percentile(a, 50))
                                       if a.size else 0.0)
            out[f"{q}_latency_p95"] = (float(np.percentile(a, 95))
                                       if a.size else 0.0)
        # hetero rollups (additive keys — homogeneous runs emit exactly
        # one tier and one shape bucket)
        for t, ts in sorted(self.tier_stats.items()):
            for k, v in sorted(ts.items()):
                out[f"tier_{t}_{k}"] = v
        for s, ss in sorted(self.shape_stats.items()):
            for k, v in sorted(ss.items()):
                out[f"shape_{s}_{k}"] = v
        if self.trunk_cache is not None:
            # hit accounting is policy-visible: exact-key hits and
            # admission rejections surface next to the hit rate so a
            # mis-tuned PopularityAdmission threshold shows up here
            # instead of as a silent hit-rate collapse
            out["cache_hits"] = self.trunk_cache.stats["hits"]
            out["cache_exact_hits"] = self.trunk_cache.stats["exact_hits"]
            out["cache_hits_hbm"] = self.trunk_cache.stats["hits_hbm"]
            out["cache_hits_host"] = self.trunk_cache.stats["hits_host"]
            out["cache_admission_rejects"] = \
                self.trunk_cache.stats["admission_rejects"]
            out["cache_hit_rate"] = self.trunk_cache.hit_rate
            out["cache_entries"] = len(self.trunk_cache)
            out["cache_bytes"] = self.trunk_cache.bytes
            # tier + index health: spills/promotions trace working-set
            # churn between the HBM budget and the host spill tier, and
            # the index name records which candidate generator served the
            # similarity path (scan oracle vs LSH)
            out["cache_index"] = self.trunk_cache.index.name
            out["cache_spills"] = self.trunk_cache.stats["spills"]
            out["cache_promotions"] = self.trunk_cache.stats["promotions"]
            out["cache_hbm_bytes"] = self.trunk_cache.tier_bytes["hbm"]
            out["cache_host_bytes"] = self.trunk_cache.tier_bytes["host"]
        return out
