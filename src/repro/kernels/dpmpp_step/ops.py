"""Public wrapper: arbitrary latent shapes -> padded tiles -> kernel.

Scalars with a batch axis ((B,) vectors) select the per-row kernel launch
— same body, per-row scalar block; see ddim_step/ops.py.  Mixed-sampler
packs invoke this on the statically-gathered dpmpp rows only (scattered
back afterwards); a full-stack compute + select would not be bitwise-safe
against the per-group oracle — see the note in ddim_step/ops.py."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels._tiles import (per_row_scalars, row_block, scalar_block,
                                  scalar_rows, tile_2d, tile_rows)
from repro.kernels.dpmpp_step.dpmpp_step import (BLOCK_C, BLOCK_R,
                                                 SCAL_WIDTH, dpmpp_step_2d,
                                                 dpmpp_step_rows)


def fused_cfg_dpmpp_step(z, eps_u, eps_c, eps_prev, guidance,
                         a_t, s_t, a_n, s_n, lam, lam_p, lam_n,
                         is_first, clip_x0: float = 0.0,
                         interpret: bool | None = None):
    """Fused CFG + DPM-Solver++(2M) update for latents of any shape (B, ...).

    Returns ``(z_next, eps_combined)`` — the combined eps feeds the solver's
    history carry, so the CFG combine never takes a separate HBM pass.  All
    step scalars (guidance, the four schedule gathers, the three lambdas
    from ``samplers.dpmpp_scalars``, clip_x0, the ``is_first`` warm-up flag)
    may be python floats or traced jnp scalars — e.g. gathered per scan
    step — and ride to the kernel in one (1, 16) block; any of them may
    instead be a (B,) vector (rows at different grid positions, the packed
    serving path), which launches the per-row variant with a (B, 16)
    scalar block.  ``is_first`` may be a traced bool (or per-row bool
    vector); it is carried as a 0/1 float and zeroes the history
    extrapolation term in-kernel (exactly the reference's ``eps_prev := eps``
    aliasing).  ``interpret=None`` resolves via dispatch (env override, else
    compiled only on TPU).
    """
    assert z.shape == eps_u.shape == eps_c.shape == eps_prev.shape
    if interpret is None:
        from repro.kernels.dispatch import resolve_interpret
        interpret = resolve_interpret()
    # layout must match the kernel's scal_ref reads (see dpmpp_step.py)
    # expm1(-h) from the same f32 lambdas the kernel reads (h = lam_n - lam)
    em1 = jnp.expm1(-(jnp.asarray(lam_n, jnp.float32)
                      - jnp.asarray(lam, jnp.float32)))
    values = (guidance, a_t, s_t, a_n, s_n, clip_x0,
              lam, lam_p, lam_n, is_first, em1)
    if per_row_scalars(*values):
        br = row_block(z[0].size, BLOCK_C, BLOCK_R)
        tiles, untile = tile_rows(br, BLOCK_C, z, eps_u, eps_c, eps_prev)
        scal = scalar_rows(values, SCAL_WIDTH, z.shape[0])
        zn, eps = dpmpp_step_rows(scal, *tiles, block_r=br,
                                  interpret=interpret)
        return untile(zn), untile(eps)
    tiles, untile = tile_2d(BLOCK_R, BLOCK_C, z, eps_u, eps_c, eps_prev)
    scal = scalar_block(values, SCAL_WIDTH)
    zn, eps = dpmpp_step_2d(scal, *tiles, interpret=interpret)
    return untile(zn), untile(eps)
