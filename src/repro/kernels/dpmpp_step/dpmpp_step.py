"""Fused CFG + DPM-Solver++(2M) update Pallas kernel.

Per sampler step the 2M solver computes (eps-parameterisation, data
prediction internally; Lu et al., 2022):

    eps     = eps_u + w (eps_c - eps_u)
    x0      = clip((z - sigma_t eps)      / alpha_t)
    x0_prev = clip((z - sigma_t eps_prev) / alpha_t)
    r       = (lambda_t - lambda_prev) / h,   h = lambda_next - lambda_t
    D       = x0 + (x0 - x0_prev) / (2 r)          # lambda-space extrapolation
    z'      = (sigma_next / sigma_t) z - alpha_next expm1(-h) D

Unfused that is the CFG combine plus two data predictions plus the history
blend — 4+ elementwise passes over 4 latent-sized tensors (z, eps_u, eps_c,
eps_prev) with combined-eps / x0 HBM round trips between them.  The kernel
computes z' AND the combined eps (next step's history carry) in one pass:
read 4 tiles, write 2.

The first-step / history-warmup edge case (branch fork restarts history too)
is handled in-kernel by a ``first`` flag scalar: the extrapolation term is
multiplied by ``1 - first``, which reproduces the reference's
``eps_prev := eps`` aliasing exactly (the term is identically zero) without
a separate warm-up launch.  All per-step scalars — guidance, the four
schedule gathers, clip, the three lambdas, the flag — ride in one (1, 16)
block mapped to every grid point.

Two launch shapes share the same kernel body (see ddim_step.py for the
rationale): :func:`dpmpp_step_2d` broadcasts ONE scalar row to the whole
batch; :func:`dpmpp_step_rows` keeps the whole (B, 16) scalar table in
SMEM and reads row ``program_id(0)``, so every row carries its own
schedule gathers, lambdas AND warm-up flag — in a packed serving
super-batch, one group can sit at its branch fork (history warm-up)
while another is mid-phase.

VMEM budget: 6 tiles x block(256, 256) x 4B = 1.5 MB  << 16 MB/core.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_R = 256
BLOCK_C = 256

# scalar block layout (1, SCAL_WIDTH) f32 — ops.py packs in this order:
#   [guidance, a_t, s_t, a_n, s_n, clip_x0, lam, lam_prev, lam_next, first,
#    expm1(lam - lam_next), 0-padding]
# expm1 is computed by the wrapper: Mosaic has no lowering for it.
SCAL_WIDTH = 16


def _kernel(scal_ref, z_ref, eu_ref, ec_ref, ep_ref, out_ref, eps_ref, *,
            per_row=False):
    r = pl.program_id(0) if per_row else 0
    w = scal_ref[r, 0]
    a_t, s_t = scal_ref[r, 1], scal_ref[r, 2]
    a_n, s_n = scal_ref[r, 3], scal_ref[r, 4]
    clip = scal_ref[r, 5]
    lam, lam_p, lam_n = scal_ref[r, 6], scal_ref[r, 7], scal_ref[r, 8]
    first = scal_ref[r, 9]
    em1 = scal_ref[r, 10]

    h = lam_n - lam
    hs = jnp.where(jnp.abs(h) > 1e-8, h, 1e-8)
    r = (lam - lam_p) / hs

    z = z_ref[...].astype(jnp.float32)
    eu = eu_ref[...].astype(jnp.float32)
    ec = ec_ref[...].astype(jnp.float32)
    ep = ep_ref[...].astype(jnp.float32)

    eps = eu + w * (ec - eu)
    inv_a = 1.0 / jnp.maximum(a_t, 1e-6)
    x0 = (z - s_t * eps) * inv_a
    x0p = (z - s_t * ep) * inv_a
    # static x0-thresholding (matches samplers.dpmpp_2m_step); clip == 0 -> off
    x0 = jnp.where(clip > 0.0, jnp.clip(x0, -clip, clip), x0)
    x0p = jnp.where(clip > 0.0, jnp.clip(x0p, -clip, clip), x0p)
    # first == 1 zeroes the history term — identical to aliasing ep := eps
    d = x0 + (1.0 - first) * (x0 - x0p) / (2.0 * jnp.maximum(r, 1e-8))
    zn = (s_n / jnp.maximum(s_t, 1e-8)) * z - a_n * em1 * d
    out_ref[...] = zn.astype(out_ref.dtype)
    eps_ref[...] = eps.astype(eps_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dpmpp_step_2d(scalars, z, eps_u, eps_c, eps_prev, interpret: bool = True):
    """z/eps_u/eps_c/eps_prev (R, C), R % BLOCK_R == 0, C % BLOCK_C == 0;
    scalars (1, SCAL_WIDTH) f32 (layout above).  Returns
    (z_next, eps_combined)."""
    R, C = z.shape
    grid = (R // BLOCK_R, C // BLOCK_C)
    tile = pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j))
    scal = pl.BlockSpec((1, SCAL_WIDTH), lambda i, j: (0, 0))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[scal, tile, tile, tile, tile],
        out_specs=(tile, tile),
        out_shape=(jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype)),
        interpret=interpret,
    )(scalars, z, eps_u, eps_c, eps_prev)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def dpmpp_step_rows(scalars, z, eps_u, eps_c, eps_prev, block_r: int,
                    interpret: bool = True):
    """Per-row-scalar variant: tensors (B, R, C) with R % block_r == 0 and
    C % BLOCK_C == 0; scalars (B, SCAL_WIDTH) f32, one row per batch
    element (layout above), resident whole in SMEM.  Returns
    (z_next, eps_combined)."""
    B, R, C = z.shape
    grid = (B, R // block_r, C // BLOCK_C)
    tile = pl.BlockSpec((1, block_r, BLOCK_C), lambda b, i, j: (b, i, j))
    scal = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_kernel, per_row=True),
        grid=grid,
        in_specs=[scal, tile, tile, tile, tile],
        out_specs=(tile, tile),
        out_shape=(jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype)),
        interpret=interpret,
    )(scalars, z, eps_u, eps_c, eps_prev)
