"""Fused CFG + DDIM update Pallas kernel.

Per sampler step SAGE (like any CFG diffusion sampler) computes

    eps = eps_u + w (eps_c - eps_u)
    z0  = (z - sigma_t eps) / alpha_t
    z'  = alpha_n z0 + sigma_n eps

Unfused, that is 3 elementwise passes over 3 latent-sized tensors (z,
eps_u, eps_c) -> 5 HBM round trips.  The kernel computes z' in one pass:
read 3 tiles, write 1.  Latents are flattened to (rows, lanes) tiles
(lane dim a multiple of 128 for the VPU); the step scalars ride in an
8-wide f32 row per scalar set.

Two launch shapes share the same kernel body:

* :func:`ddim_step_2d` — whole batch as one (rows, lanes) grid, ONE
  scalar row broadcast to every tile (per-group execution: all rows sit
  at the same grid position);
* :func:`ddim_step_rows` — (B, rows, lanes) grid with the whole (B, 8)
  scalar table resident in SMEM, read at row ``program_id(0)``, so every
  row carries its OWN (a_t, s_t, a_n, s_n) — the packed serving path,
  where one super-batch mixes groups at different positions on the DDIM
  grid.  (A per-row (1, 8) VMEM block would break Mosaic's tiling rule:
  a block's last two dims must be multiples of (8, 128) or span the
  array.)

VMEM budget: 4 tiles x block(256, 256) x 4B = 1 MB  << 16 MB/core.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_R = 256
BLOCK_C = 256


def _kernel(scal_ref, z_ref, eu_ref, ec_ref, out_ref, *, per_row=False):
    r = pl.program_id(0) if per_row else 0
    w = scal_ref[r, 0]
    a_t, s_t = scal_ref[r, 1], scal_ref[r, 2]
    a_n, s_n = scal_ref[r, 3], scal_ref[r, 4]
    clip = scal_ref[r, 5]
    z = z_ref[...].astype(jnp.float32)
    eu = eu_ref[...].astype(jnp.float32)
    ec = ec_ref[...].astype(jnp.float32)
    eps = eu + w * (ec - eu)
    z0 = (z - s_t * eps) / jnp.maximum(a_t, 1e-6)
    # static x0-thresholding (matches samplers.ddim_step); clip == 0 -> off
    z0 = jnp.where(clip > 0.0, jnp.clip(z0, -clip, clip), z0)
    out_ref[...] = (a_n * z0 + s_n * eps).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ddim_step_2d(scalars, z, eps_u, eps_c, interpret: bool = True):
    """z/eps_u/eps_c (R, C), R % BLOCK_R == 0 and C % BLOCK_C == 0;
    scalars (1, 8) f32 = [guidance, a_t, s_t, a_n, s_n, clip_x0, 0, 0]."""
    R, C = z.shape
    grid = (R // BLOCK_R, C // BLOCK_C)
    tile = pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j))
    scal = pl.BlockSpec((1, 8), lambda i, j: (0, 0))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[scal, tile, tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(z.shape, z.dtype),
        interpret=interpret,
    )(scalars, z, eps_u, eps_c)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def ddim_step_rows(scalars, z, eps_u, eps_c, block_r: int,
                   interpret: bool = True):
    """Per-row-scalar variant: z/eps_u/eps_c (B, R, C) with
    R % block_r == 0 and C % BLOCK_C == 0; scalars (B, 8) f32, one
    [guidance, a_t, s_t, a_n, s_n, clip_x0, 0, 0] row per batch element.
    Same kernel body as :func:`ddim_step_2d` — the batch grid axis selects
    both the latent tile and its scalar row of the SMEM table."""
    B, R, C = z.shape
    grid = (B, R // block_r, C // BLOCK_C)
    tile = pl.BlockSpec((1, block_r, BLOCK_C), lambda b, i, j: (b, i, j))
    scal = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_kernel, per_row=True),
        grid=grid,
        in_specs=[scal, tile, tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(z.shape, z.dtype),
        interpret=interpret,
    )(scalars, z, eps_u, eps_c)
