"""Compile the serving path's kernels for a described TPU v5e (no chip).

The TPU compiler is installed next to JAX and compiles for a chip that
is described but not attached, so Mosaic refusals (block tiling rules,
unlowerable primitives, VMEM overuse) surface here instead of on the
chip.  Interpret-mode tests cannot see them.  Nothing runs: these tests
check that each program compiles with ``interpret=False`` and holds a
Pallas kernel, at sage-dit's published widths.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU library, and every
test-runner worker imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import get_config, replace
from repro.kernels._tiles import row_block
from repro.kernels.ddim_step import ddim_step as ddim_k
from repro.kernels.dpmpp_step import dpmpp_step as dpmpp_k
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.group_mean.ops import masked_group_mean
from repro.models import dit

ROWS = 10                          # packed rows of one launch
LATENT = (64, 64, 4)               # sage-dit's published latent


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _step_tiles(block_r):
    n = LATENT[0] * LATENT[1] * LATENT[2]
    rows = -(-n // ddim_k.BLOCK_C)
    return -(-rows // block_r) * block_r


@pytest.mark.parametrize("kernel", ["ddim", "dpmpp"])
def test_step_rows_kernel_compiles(one_chip, kernel):
    """The per-row scalar launch of the packed serving path: one scalar
    row per batch element, B=10 rows over 64x64x4 latents."""
    mod, width, n_in = ((ddim_k, 8, 3) if kernel == "ddim"
                        else (dpmpp_k, dpmpp_k.SCAL_WIDTH, 4))
    n = LATENT[0] * LATENT[1] * LATENT[2]
    br = row_block(n, mod.BLOCK_C, mod.BLOCK_R)
    tile = _spec(one_chip, (ROWS, _step_tiles(br), mod.BLOCK_C))
    fn = getattr(mod, f"{kernel}_step_rows")
    _compile(functools.partial(fn, block_r=br, interpret=False),
             _spec(one_chip, (ROWS, width)), *[tile] * n_in)


@pytest.mark.parametrize("kernel", ["ddim", "dpmpp"])
def test_step_2d_kernel_compiles(one_chip, kernel):
    """The broadcast launch of the per-group path (one scalar row)."""
    mod, width, n_in = ((ddim_k, 8, 3) if kernel == "ddim"
                        else (dpmpp_k, dpmpp_k.SCAL_WIDTH, 4))
    rows = _step_tiles(mod.BLOCK_R) * ROWS
    tile = _spec(one_chip, (rows, mod.BLOCK_C))
    fn = getattr(mod, f"{kernel}_step_2d")
    _compile(functools.partial(fn, interpret=False),
             _spec(one_chip, (1, width)), *[tile] * n_in)


@pytest.mark.parametrize("keys", [1024, 77], ids=["self", "cross"])
def test_flash_attention_head_dim_72_compiles(one_chip, keys):
    """sage-dit attention: 16 heads of 72 over 1,024 latent tokens,
    against itself or against the 77 text tokens."""
    cfg = get_config("sage-dit")
    h, d = cfg.n_heads, cfg.hd
    q = _spec(one_chip, (2, dit.n_tokens(cfg), h, d), jnp.bfloat16)
    kv = _spec(one_chip, (2, keys, h, d), jnp.bfloat16)
    _compile(functools.partial(flash_attention, causal=False,
                               interpret=False), q, kv, kv)


def test_masked_group_mean_compiles(one_chip):
    """Shared-uncond group mean over 2 groups of 4 latents."""
    x = _spec(one_chip, (2, 4) + LATENT)
    mask = _spec(one_chip, (2, 4))
    _compile(functools.partial(masked_group_mean, interpret=False), x, mask)


def test_dit_forward_published_width_compiles(one_chip, monkeypatch):
    """One published-width denoiser evaluation (28 layers, d_model 1152)
    of 10 rows with the flash kernel, params as shapes only."""
    monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
    cfg = replace(get_config("sage-dit"), attn_impl="pallas",
                  kernel_interpret="off")
    params = jax.eval_shape(lambda k: dit.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                          params)
    z = _spec(one_chip, (ROWS,) + LATENT)
    t = _spec(one_chip, (ROWS,), jnp.int32)
    cond = _spec(one_chip, (ROWS, cfg.cond_len, cfg.cond_dim))
    compiled = _compile(lambda p, z, t, c: dit.forward(p, cfg, z, t, c),
                        params, z, t, cond)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
