"""Import-safe roofline cost model (per-chip peaks keyed by device kind).

``launch/dryrun.py`` owns the *measured* roofline (lower + compile every
(arch x shape) on the production mesh and read XLA's cost analysis), but
importing it has a deliberate side effect: it forces
``--xla_force_host_platform_device_count=512`` into ``XLA_FLAGS`` before
JAX initialises, which is exactly wrong for anything that is not a
dry-run.  This module holds the shared per-chip peak table and the small
closed-form predictors that the serving telemetry reports
(``serving/reports.py``) need, with no JAX import and no environment
mutation; ``dryrun.py`` reads its peaks from here so there is a single
source of truth.

The predictors are deliberately first-order: they model the scheduler's
*tick economics* (segments per phase, rows per launch, NFE ledger), not
XLA's fusion choices.  Their job in a capacity report is to make the gap
between "what the tick loop should have cost" and "what the telemetry
says it cost" visible — pad waste, cache savings, retry waste, and
stalls are exactly that gap.
"""
from __future__ import annotations

from dataclasses import dataclass

# ---------------------------------------------------------------------------
# Per-chip peaks, keyed by ``jax.Device.device_kind``
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ChipPeaks:
    """Published peaks of ONE chip."""
    flops: float              # bf16 FLOP/s
    hbm_bw: float             # HBM B/s
    ici_bw: float             # B/s per interconnect link


#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect over 4
#: links).  Add a kind only with its published source.
PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks for a ``device_kind``; an unknown kind raises (never a
    default — a roofline against the wrong chip is a wrong number)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak table entry for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def denoiser_flops_per_eval(n_params: float, n_tokens: int) -> float:
    """FLOPs of ONE denoiser evaluation of one latent row.

    2 FLOPs per param per token (matmul fwd), doubled for CFG's
    unconditional+conditional pair — the same convention as dryrun's
    ``sage_serve`` model-flops term.
    """
    return 2.0 * n_params * 2 * n_tokens


def roofline_seconds(peaks: ChipPeaks, flops: float, bytes_acc: float = 0.0,
                     coll_bytes: float = 0.0, chips: int = 1) -> float:
    """Lower-bound wall seconds: the max of the three roofline terms."""
    c = max(chips, 1)
    return max(flops / c / peaks.flops, bytes_acc / c / peaks.hbm_bw,
               coll_bytes / peaks.ici_bw)


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b) if b else 0


@dataclass(frozen=True)
class DrainPrediction:
    """Closed-form tick economics of draining a request set."""
    groups: int
    shared_segments: int      # per group
    branch_segments: int      # per group
    ticks: int                # predicted ticks-to-drain
    nfe: int                  # predicted NFE (no cache, no faults)
    nfe_independent: int      # per-request baseline the saving is vs.


def predict_drain(requests: int, group_size: int, total_steps: int,
                  n_shared: int, slice_steps: int,
                  max_groups_per_tick: int | None = None,
                  ) -> DrainPrediction:
    """Predict ticks-to-drain and NFE for ``requests`` similar requests.

    Assumes full groups of ``group_size`` (the grouping optimum), no
    trunk-cache hits, no faults: one segment per selected group per
    tick, shared phase charging 1 NFE-row per step per group and branch
    charging ``group_size`` rows per step.  Under a
    ``max_groups_per_tick`` cap the in-flight set advances in waves of
    ``cap`` groups.  Observed ticks above this are queueing + holds +
    retries; observed NFE below it is cache savings — the capacity
    report prints both gaps.
    """
    if requests <= 0 or total_steps <= 0:
        return DrainPrediction(0, 0, 0, 0, 0, 0)
    group_size = max(group_size, 1)
    slice_steps = max(slice_steps, 1)
    n_shared = min(max(n_shared, 0), total_steps)
    groups = _ceil_div(requests, group_size)
    shared_segs = _ceil_div(n_shared, slice_steps)
    branch_segs = _ceil_div(total_steps - n_shared, slice_steps)
    per_group_ticks = shared_segs + branch_segs
    if max_groups_per_tick is None or groups <= max_groups_per_tick:
        ticks = per_group_ticks
    else:
        ticks = per_group_ticks * _ceil_div(groups, max_groups_per_tick)
    nfe = groups * n_shared + requests * (total_steps - n_shared)
    return DrainPrediction(groups, shared_segs, branch_segs, ticks, nfe,
                           requests * total_steps)
