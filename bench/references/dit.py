"""Plain reference for the sage-dit family: text tower, DiT denoiser and
the SAGE shared/branch DDIM trajectory with classifier-free guidance.

Written from the published description and the configuration file
(``bench/configs/<config>.json``); it imports nothing of the program.
Every matrix product goes through :class:`Matmul`: ``"f32"`` is float32
at ``Precision.HIGHEST`` (the reference); ``"fp8"`` is the denoiser
computed in float8 e4m3, the step below the configuration's bfloat16
compute dtype (the control): both operands of every product quantized
with one absmax scale per tensor (float32 accumulation), and the
residual stream stored in e4m3 after every update, where the program
stores it in bfloat16.  Norms, softmax and the solver are float32.

Departures from DiT-XL/2 as published are the configuration's
``assumed`` list: qk-norm, 1-D RoPE in self-attention, cross-attention
to the text features after each self-attention, and a random byte-level
text tower.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PAD, BOS = 257, 256
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


@dataclass(frozen=True)
class Matmul:
    """The precision every matrix product of the reference runs at."""
    mode: str = "f32"            # "f32" | "bf16" | "fp8" (the control)

    def __call__(self, spec: str, a, b):
        if self.mode == "f32":
            return jnp.einsum(spec, a.astype(jnp.float32),
                              b.astype(jnp.float32),
                              precision=jax.lax.Precision.HIGHEST)
        if self.mode == "bf16":
            return jnp.einsum(spec, a.astype(jnp.bfloat16),
                              b.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
        if self.mode != "fp8":
            raise ValueError(f"unknown matmul mode {self.mode!r}")
        qa, sa = _quant8(a)
        qb, sb = _quant8(b)
        # e4m3 products are exact in bf16, so one bf16 pass with f32
        # accumulation computes the fp8 matmul exactly
        out = jnp.einsum(spec, qa, qb, preferred_element_type=jnp.float32)
        return out * (sa * sb)

    def act(self, x):
        """An activation as the compute dtype stores it: unchanged in
        float32, rounded to e4m3 (one absmax scale) under ``fp8``."""
        if self.mode == "f32":
            return x
        if self.mode == "bf16":
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        q, s = _quant8(x)
        return q.astype(jnp.float32) * s


def _quant8(x):
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.bfloat16), s


# -- shared pieces -------------------------------------------------------

def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + scale)


def layer_norm(x, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def rope(x, theta):
    """x (B, S, H, hd); rotate-half RoPE over positions 0..S-1."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(mm: Matmul, p, x, kv, heads: int, *, causal=False,
              qk_eps=None, theta=None):
    """Multi-head attention of x (B, S, d) over kv (B, Sk, d)."""
    B, S, _ = x.shape
    Sk = kv.shape[1]
    q = mm("bsd,de->bse", x, p["wq"]).reshape(B, S, heads, -1)
    k = mm("bsd,de->bse", kv, p["wk"]).reshape(B, Sk, heads, -1)
    v = mm("bsd,de->bse", kv, p["wv"]).reshape(B, Sk, heads, -1)
    if qk_eps is not None:
        q = rms(q, p["q_norm"], qk_eps)
        k = rms(k, p["k_norm"], qk_eps)
    if theta is not None:
        q, k = rope(q, theta), rope(k, theta)
    s = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, Sk), bool)), s,
                      jnp.finfo(jnp.float32).min)
    o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return mm("bse,ed->bsd", o.reshape(B, S, -1), p["wo"])


# -- text tower -----------------------------------------------------------

def tokenize(prompts: Sequence[str], max_len: int) -> np.ndarray:
    """Bytes offset 0..255, BOS 256 first, PAD 257 after the text."""
    out = np.full((len(prompts), max_len), PAD, np.int32)
    for i, s in enumerate(prompts):
        b = list(s.encode("utf-8"))[:max_len - 2]
        out[i, 0] = BOS
        out[i, 1:1 + len(b)] = b
    return out


def encode_text(mm: Matmul, tp, tspec: dict, tokens):
    """tokens (B, L) -> (features (B, L, d), pooled unit vectors (B, d)):
    pre-norm causal blocks, final RMS norm, mean over non-PAD tokens."""
    x = tp["embed"][tokens]
    heads, theta = tspec["n_heads"], tspec["rope_theta"]

    def block(x, bp):
        x = x + attention(mm, bp["attn"], rms(x, bp["ln1"], 1e-6),
                          rms(x, bp["ln1"], 1e-6), heads, causal=True,
                          theta=theta)
        h = rms(x, bp["ln2"], 1e-6)
        x = x + mm("bsf,fd->bsd", gelu_tanh(mm("bsd,df->bsf", h,
                                               bp["mlp"]["wi"])),
                   bp["mlp"]["wo"])
        return x, None

    x, _ = jax.lax.scan(block, x, tp["blocks"])
    x = rms(x, tp["ln_f"], 1e-6)
    keep = (tokens != PAD).astype(jnp.float32)[..., None]
    pooled = jnp.sum(x * keep, 1) / jnp.maximum(jnp.sum(keep, 1), 1.0)
    return x, pooled / jnp.linalg.norm(pooled, axis=-1, keepdims=True)


# -- DiT --------------------------------------------------------------------

def patchify(z, p):
    B, H, W, C = z.shape
    z = z.reshape(B, H // p, p, W // p, p, C).transpose(0, 1, 3, 2, 4, 5)
    return z.reshape(B, (H // p) * (W // p), p * p * C)


def unpatchify(x, p, C):
    B, n, _ = x.shape
    h = int(math.isqrt(n))
    x = x.reshape(B, h, h, p, p, C).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, h * p, h * p, C)


def timestep_embedding(t, dim):
    half = dim // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half) / half)
    ang = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], -1)


def denoise(mm: Matmul, params, spec: dict, z, t, cond):
    """eps prediction: z (B, H, W, C), t (B,), cond (B, Lc, dc)."""
    heads, eps = spec["n_heads"], spec["norm_eps"]
    x = mm("bsp,pd->bsd", patchify(z, spec["patch"]), params["patch_in"])
    x = mm.act(x + params["pos"][None])
    te = timestep_embedding(t, spec["timestep_dim"])
    te = mm("bd,de->be", jax.nn.silu(mm("bd,de->be", te, params["t_w1"])),
            params["t_w2"])
    c = mm("bsd,de->bse", cond, params["cond_proj"])
    tmod = jax.nn.silu(te)
    qk = eps if spec["qk_norm"] else None

    def block(x, bp):
        mod = mm("bd,de->be", tmod, bp["adaln"]) + bp["adaln_b"]
        sh1, sc1, g1, sh2, sc2, g2 = (m[:, None, :]
                                      for m in jnp.split(mod, 6, -1))
        h = layer_norm(x) * (1.0 + sc1) + sh1
        x = mm.act(x + g1 * attention(mm, bp["attn"], h, h, heads,
                                      qk_eps=qk, theta=spec["rope_theta"]))
        hx = layer_norm(x) * (1.0 + bp["lnx"])
        x = mm.act(x + attention(mm, bp["xattn"], hx, c, heads, qk_eps=qk))
        h = layer_norm(x) * (1.0 + sc2) + sh2
        m = mm("bsf,fd->bsd", gelu_tanh(mm("bsd,df->bsf", h,
                                           bp["mlp"]["wi"])),
               bp["mlp"]["wo"])
        return mm.act(x + g2 * m), None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    fmod = mm("bd,de->be", tmod, params["final_adaln"]) + \
        params["final_adaln_b"]
    shf, scf = (m[:, None, :] for m in jnp.split(fmod, 2, -1))
    x = layer_norm(x) * (1.0 + scf) + shf
    out = mm("bsd,dp->bsp", x, params["out"])
    return unpatchify(out, spec["patch"], spec["latent_channels"])


# -- sampler ----------------------------------------------------------------

def cosine_schedule(T: int = 1000):
    """VP cosine schedule: (alphas, sigmas) at t = 0..T, float32."""
    s = np.linspace(0.0, 1.0, T + 1)
    f = np.cos((s + 0.008) / 1.008 * np.pi / 2) ** 2
    abar = np.clip(f / f[0], 1e-8, 1.0)
    return (np.sqrt(abar).astype(np.float32),
            np.sqrt(1.0 - abar).astype(np.float32))


def ddim_grid(T: int, steps: int) -> np.ndarray:
    return np.linspace(T, 0, steps + 1).round().astype(np.int64)


def n_shared_steps(total: int, share_ratio: float) -> int:
    """Steps before the branch point: T - round(T * (1 - beta))."""
    return total - int(round(total * (1.0 - share_ratio)))


class Trajectories:
    """CFG + DDIM trajectories of SAGE groups under one precision.

    ``step`` is one jitted sampler step over a batch of rows: the
    denoiser on [z; z] under [null; cond], eps = eps_u + w (eps_c - eps_u),
    DDIM with x0 clipped to +-clip_x0."""

    def __init__(self, spec: dict, sage: dict, params, text_params,
                 mode: str):
        self.spec, self.sage, self.params = spec, sage, params
        self.text_params = text_params
        self.mm = Matmul(mode)
        self.alphas, self.sigmas = cosine_schedule(1000)
        self.grid = ddim_grid(1000, sage["total_steps"])
        w, clip = float(sage["guidance_scale"]), float(sage["clip_x0"])
        mm = self.mm

        def step(params, z, t, t_next, cond, a_t, s_t, a_n, s_n):
            B = z.shape[0]
            zz = jnp.concatenate([z, z], 0)
            tt = jnp.full((2 * B,), t, jnp.int32)
            cc = jnp.concatenate([jnp.zeros_like(cond), cond], 0)
            e = denoise(mm, params, spec, zz, tt, cc)
            e = e[:B] + w * (e[B:] - e[:B])
            z0 = jnp.clip((z - s_t * e) / jnp.maximum(a_t, 1e-6),
                          -clip, clip)
            return a_n * z0 + s_n * e

        self._step = jax.jit(step)

    def run(self, z, cond, start: int, stop: int):
        """Advance rows z (B, H, W, C) under cond (B, Lc, dc) from grid
        position ``start`` to ``stop``."""
        for i in range(start, stop):
            t, tn = int(self.grid[i]), int(self.grid[i + 1])
            z = self._step(self.params, z, t, tn, cond,
                           self.alphas[t], self.sigmas[t],
                           self.alphas[tn], self.sigmas[tn])
        return z


def group_noise(sched_seed: int, gid: int, shape) -> jax.Array:
    """The group's initial latent as the scheduler defines it: a standard
    normal (1, H, W, C) from PRNGKey(seed) folded with 0x5A9E, then with
    the group id (a trajectory depends only on the group's identity)."""
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(sched_seed), 0x5A9E), gid)
    return jax.random.normal(key, (1,) + tuple(shape), jnp.float32)


def embed(spec: dict, text_params, prompts: Sequence[str]):
    """(features (B, Lc, dc), pooled (B, d)) as numpy arrays, at the
    reference precision (the control changes the denoiser only)."""
    tspec = spec["text_tower"]
    toks = jnp.asarray(tokenize(prompts, spec["cond_len"]))
    f, p = jax.jit(lambda tp, tk: encode_text(Matmul("f32"), tp, tspec,
                                              tk))(text_params, toks)
    return np.asarray(f), np.asarray(p)


def group_latents(traj: Trajectories, spec: dict, sched_seed: int,
                  groups: Dict[int, List[str]], jobs: List[dict]):
    """Final latents for ``jobs``: each ``{"gid", "source", "members"}``
    where ``members`` indexes the prompts of group ``gid`` to compute and
    ``source`` is the group whose trunk the branch starts from (``gid``
    itself unless the trunk came from the cache).  ``groups`` maps group
    id -> member prompts.  Returns {(gid, member index): latent (H,W,C)}.
    """
    shape = (spec["latent_size"], spec["latent_size"],
             spec["latent_channels"])
    n_sh = n_shared_steps(traj.sage["total_steps"],
                          traj.sage["share_ratio"])
    total = traj.sage["total_steps"]
    trunks = {}
    out = {}
    for job in jobs:
        src = job["source"]
        if src not in trunks:
            feats, _ = embed(spec, traj.text_params, groups[src])
            cbar = jnp.asarray(feats.mean(0, keepdims=True))
            trunks[src] = traj.run(group_noise(sched_seed, src, shape),
                                   cbar, 0, n_sh)
        feats, _ = embed(spec, traj.text_params, groups[job["gid"]])
        idx = list(job["members"])
        cond = jnp.asarray(feats[idx])
        z = jnp.broadcast_to(trunks[src], (len(idx),) + shape)
        z = np.asarray(traj.run(z, cond, n_sh, total))
        for j, m in enumerate(idx):
            out[(job["gid"], m)] = z[j]
    return out
