"""Random weights for the benchmark, drawn on the device in one jitted
program each.

The trees are laid out as the program's ``models.dit.forward`` and
``models.text_encoder.encode_text`` read them (the checkpoint layout the
benchmark serves), but they are drawn here, from the run's seed, so that
the plain reference can be given the same weights without taking
anything the program made.

Recipe (copied from ``chip_smoke.smoke_params``, PR 12): projections are
normal / sqrt(fan_in); the adaLN, ``lnx`` and final adaLN leaves are small
normals (0.02) instead of DiT's adaLN-zero, so every attention and MLP
block reaches the output (with adaLN-zero a broken attention would change
nothing); the output projection is 0.3 / sqrt(fan_in): large enough that
the network, not the initial noise, decides the latents, small enough
that 30 guided steps do not turn bf16 rounding into unrelated latents.

The text tower stands in for a pretrained encoder, which a deployment
does not redraw: its weights come from a fixed key, not the seed, so the
prompt-similarity structure the traffic was checked against is the same
in every run.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: the text tower's fixed key (a deployment's encoder does not change)
TEXT_KEY = 0x7E47


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from all 64 bits of ``seed`` (``PRNGKey`` alone keeps
    only the low 32 without x64)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _dense(key, d_in: int, d_out: int, lead=(), gain: float = 1.0):
    return jax.random.normal(key, lead + (d_in, d_out), jnp.float32) * (
        gain / math.sqrt(d_in))


def _small(key, shape):
    return 0.02 * jax.random.normal(key, shape, jnp.float32)


def _attn(key, d: int, heads: int, hd: int, lead, qk_norm: bool):
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {"wq": _dense(kq, d, heads * hd, lead),
         "wk": _dense(kk, d, heads * hd, lead),
         "wv": _dense(kv, d, heads * hd, lead),
         "wo": _dense(ko, heads * hd, d, lead)}
    if qk_norm:
        p["q_norm"] = jnp.zeros(lead + (hd,), jnp.float32)
        p["k_norm"] = jnp.zeros(lead + (hd,), jnp.float32)
    return p


def dit_tree(spec: dict, key: jax.Array) -> dict:
    """The DiT parameter tree for config ``spec`` (bench/configs/*.json)."""
    d, L = spec["d_model"], spec["n_layers"]
    H, hd, ff = spec["n_heads"], spec["head_dim"], spec["d_ff"]
    p_in = spec["patch"] ** 2 * spec["latent_channels"]
    n_tok = (spec["latent_size"] // spec["patch"]) ** 2
    tdim = spec["timestep_dim"]
    ks = iter(jax.random.split(key, 16))
    lead = (L,)
    blocks = {
        "adaln": _small(next(ks), (L, d, 6 * d)),
        "adaln_b": _small(next(ks), (L, 6 * d)),
        "attn": _attn(next(ks), d, H, hd, lead, spec["qk_norm"]),
        "lnx": _small(next(ks), (L, d)),
        "xattn": _attn(next(ks), d, H, hd, lead, spec["qk_norm"]),
        "mlp": {"wi": _dense(next(ks), d, ff, lead),
                "wo": _dense(next(ks), ff, d, lead)},
    }
    return {
        "patch_in": _dense(next(ks), p_in, d),
        "pos": _small(next(ks), (n_tok, d)),
        "t_w1": _dense(next(ks), tdim, d),
        "t_w2": _dense(next(ks), d, d),
        "cond_proj": _dense(next(ks), spec["cond_dim"], d),
        "blocks": blocks,
        "final_adaln": _small(next(ks), (d, 2 * d)),
        "final_adaln_b": _small(next(ks), (2 * d,)),
        "out": _dense(next(ks), d, p_in, gain=0.3),
    }


def text_tree(tspec: dict, key: jax.Array) -> dict:
    """The text tower's tree (``text_tower`` of a config spec)."""
    d, L = tspec["d_model"], tspec["layers"]
    ks = iter(jax.random.split(key, 8))
    lead = (L,)
    return {
        "embed": _small(next(ks), (tspec["vocab"], d)),
        "blocks": {
            "ln1": jnp.zeros(lead + (d,), jnp.float32),
            "attn": _attn(next(ks), d, tspec["n_heads"],
                          d // tspec["n_heads"], lead, False),
            "ln2": jnp.zeros(lead + (d,), jnp.float32),
            "mlp": {"wi": _dense(next(ks), d, tspec["d_ff"], lead),
                    "wo": _dense(next(ks), tspec["d_ff"], d, lead)},
        },
        "ln_f": jnp.zeros((d,), jnp.float32),
    }


def draw(spec: dict, seed: int):
    """(dit params, text params) on the default device: the DiT from
    ``seed``, the text tower from :data:`TEXT_KEY`.  One compiled program
    each; the key is a traced argument, so every seed reuses it."""
    dit = jax.jit(lambda k: dit_tree(spec, k))(seed_key(seed))
    text = jax.jit(lambda k: text_tree(spec["text_tower"], k))(
        jax.random.PRNGKey(TEXT_KEY))
    return jax.block_until_ready((dit, text))
