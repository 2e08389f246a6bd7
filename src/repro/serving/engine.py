"""SAGE diffusion serving engine (the paper's deployment surface).

Request lifecycle:
  submit(prompts) -> [queue] -> embed (text tower) -> semantic grouping
  (greedy cliques over the tau threshold graph) -> pad to the static group
  width N -> Alg. 1 shared sampling (jit per (K, N, T*) bucket) -> VAE
  decode -> responses + NFE accounting.

The sampling machinery lives in ``repro.serving.scheduler``: ``step()``
delegates to :meth:`RequestScheduler.run_batch`, the synchronous special
case of the continuous-batching tick loop (whole-phase segments, no
arrivals, no trunk cache).  For arrival-driven serving with cross-batch
trunk reuse, drive the scheduler directly — see
:meth:`SageServingEngine.streaming_scheduler` and
``examples/serve_shared.py --streaming``.

Adaptive branch point (paper §2.2 option): T* is chosen from each group's
own min pairwise similarity and snapped to a small bucket set so each
bucket compiles once (one packed sampler call per bucket — a singleton
group's pinned min-sim no longer drags other groups' buckets).

Edge semantics for grouping (which cosine similarities count as "similar
enough") are defined once in ``core.grouping.edge_mask`` — the
(tau_min, tau_max] convention — not re-encoded here.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax

from repro.config import ModelConfig, SageConfig
from repro.config import replace as config_replace
from repro.core.schedule import Schedule, make_schedule
from repro.models import dit
from repro.models import text_encoder as te
from repro.serving.scheduler import Completed, RequestScheduler
from repro.serving.telemetry import MetricsRegistry, Tracer
from repro.serving.trunk_cache import TrunkCache

__all__ = ["Completed", "SageServingEngine", "build_engine"]


class SageServingEngine:
    def __init__(self, model_cfg: ModelConfig, sage: SageConfig,
                 dit_params, text_params, text_cfg, vae_params=None,
                 sched: Optional[Schedule] = None, group_size: int = 4,
                 branch_buckets: Sequence[float] = (0.2, 0.3, 0.4),
                 seed: int = 0, attn_impl: Optional[str] = None,
                 step_impl: Optional[str] = None,
                 kernel_interpret: Optional[str] = None,
                 policy: str = "eager", tracer: Optional[Tracer] = None,
                 metrics: Optional[MetricsRegistry] = None):
        """attn_impl / step_impl / kernel_interpret override the kernel
        backend knobs of model_cfg / sage (see repro.kernels.dispatch):
        attn_impl="pallas" + step_impl="fused" runs the whole sampling hot
        path on the Pallas kernels.  ``policy`` is the launch policy
        (``serving.policies``) inherited by :meth:`streaming_scheduler`;
        the synchronous :meth:`step` path has no arrivals to hold for, so
        the policy only matters for streaming.  ``tracer``/``metrics``
        (``serving.telemetry``) are forwarded to the internal scheduler;
        a streaming scheduler wants its own (registry prefixes are
        claimed per scheduler) — pass them via
        :meth:`streaming_scheduler` kwargs instead."""
        if attn_impl is not None:
            model_cfg = config_replace(model_cfg, attn_impl=attn_impl)
        if kernel_interpret is not None:
            model_cfg = config_replace(model_cfg,
                                       kernel_interpret=kernel_interpret)
            sage = config_replace(sage, kernel_interpret=kernel_interpret)
        if step_impl is not None:
            sage = config_replace(sage, step_impl=step_impl)
        self.cfg = model_cfg
        self.sage = sage
        self.sched = sched or make_schedule(1000)
        self.dit_params = dit_params
        self.text_params = text_params
        self.text_cfg = text_cfg
        self.vae_params = vae_params
        self.group_size = group_size
        self.branch_buckets = branch_buckets
        self.seed = seed
        self.policy = policy
        self.queue: List[str] = []
        self.scheduler = RequestScheduler(
            model_cfg, sage, dit_params, text_params, text_cfg,
            vae_params=vae_params, sched=self.sched, group_size=group_size,
            branch_buckets=branch_buckets, policy=policy, seed=seed,
            tracer=tracer, metrics=metrics)

    # ------------------------------------------------------------------
    def submit(self, prompts: Sequence[str]) -> None:
        self.queue.extend(prompts)

    def step(self, max_batch: int = 32, adaptive: Optional[bool] = None
             ) -> List[Completed]:
        """Serve one engine iteration over up to max_batch queued prompts."""
        if not self.queue:
            return []
        prompts = self.queue[:max_batch]
        self.queue = self.queue[max_batch:]
        return self.scheduler.run_batch(prompts, adaptive=adaptive)

    def streaming_scheduler(self, slice_steps: int = 4,
                            max_wait_ticks: int = 2,
                            trunk_cache: Optional[TrunkCache] = None,
                            **kw) -> RequestScheduler:
        """A fresh continuous-batching scheduler over this engine's model
        (arrival-driven ticks + optional cross-batch trunk cache); the
        engine's own synchronous scheduler and stats are untouched.
        Heterogeneous-serving knobs (``tiers``, ``mix_samplers``,
        ``degrade_tier``, qos/admission, telemetry) forward through
        ``**kw`` — per-request shape/tier/sampler are then chosen at
        ``submit()`` time on the returned scheduler."""
        kw.setdefault("seed", self.seed)
        kw.setdefault("policy", self.policy)
        return RequestScheduler(
            self.cfg, self.sage, self.dit_params, self.text_params,
            self.text_cfg, vae_params=self.vae_params, sched=self.sched,
            group_size=self.group_size, branch_buckets=self.branch_buckets,
            slice_steps=slice_steps, max_wait_ticks=max_wait_ticks,
            trunk_cache=trunk_cache, **kw)

    @property
    def stats(self):
        return self.scheduler.stats

    @property
    def cost_saving(self) -> float:
        return self.scheduler.cost_saving


def build_engine(cfg: ModelConfig, sage: SageConfig, *, seed: int = 0,
                 dit_params=None, **kw) -> SageServingEngine:
    """An engine over random weights drawn from ``seed``: the DiT from
    ``PRNGKey(seed)`` (unless ``dit_params`` is given) and a 2-layer text
    tower at ``cfg.cond_dim`` from ``PRNGKey(seed + 1)``.  ``kw`` forwards
    to :class:`SageServingEngine` (``group_size``, ``attn_impl``, ...)."""
    tc = te.text_cfg(dim=cfg.cond_dim, layers=2)
    if dit_params is None:
        dit_params = dit.init_params(cfg, jax.random.PRNGKey(seed))
    return SageServingEngine(
        cfg, sage, dit_params=dit_params,
        text_params=te.init_text(jax.random.PRNGKey(seed + 1), tc),
        text_cfg=tc, **kw)
