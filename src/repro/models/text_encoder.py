"""Two-tower CLIP-style encoders.

* text tower: byte-level causal transformer; per-token features feed the
  DiT cross-attention (the `c` of Alg. 1/2) and the EOS-pooled, L2-normalised
  embedding drives semantic grouping (cosine similarity, paper §2.2) and the
  CLIP-proxy metric.
* image tower: small patch transformer for the CLIP-proxy metric.

``contrastive_loss`` trains both towers jointly on (image, prompt) pairs so
the proxy metric is meaningful offline (no pretrained CLIP available).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, replace, get_config
from repro.models import attention as attn
from repro.models.layers import apply_mlp, dense_init, dot, init_mlp, rms_norm

Params = Dict[str, Any]


def text_cfg(dim: int = 256, layers: int = 4, vocab: int = 258) -> ModelConfig:
    return ModelConfig(name="text-tower", family="dense", n_layers=layers,
                       d_model=dim, n_heads=4, n_kv_heads=4, d_ff=4 * dim,
                       vocab=vocab, mlp_kind="gelu")


def init_text(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, cfg.n_layers + 2)

    def blk(k):
        k1, k2 = jax.random.split(k)
        return {"ln1": jnp.zeros((cfg.d_model,)),
                "attn": attn.init_gqa(k1, cfg),
                "ln2": jnp.zeros((cfg.d_model,)),
                "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp_kind)}

    return {"embed": jax.random.normal(ks[0], (cfg.vocab, cfg.d_model)) * 0.02,
            "blocks": jax.vmap(blk)(jax.random.split(ks[1], cfg.n_layers)),
            "ln_f": jnp.zeros((cfg.d_model,))}


def _block(x: jax.Array, bp: Params, cfg: ModelConfig,
           causal: bool = True) -> Tuple[jax.Array, None]:
    """One pre-norm tower block, as a ``lax.scan`` body over stacked params."""
    x = x + attn.gqa_full(bp["attn"], cfg, rms_norm(x, bp["ln1"]),
                          causal=causal)
    x = x + apply_mlp(bp["mlp"], rms_norm(x, bp["ln2"]), cfg.mlp_kind)
    return x, None


@functools.partial(jax.jit, static_argnums=1)
def text_stack(blocks: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """The text tower's layer stack, compiled once per (cfg, x shape).

    A scan built eagerly around a fresh closure is re-traced and re-lowered
    on every call; this one jit is reused.  Only the stack is jitted: the
    eager ops around it in :func:`encode_text` keep their numerics."""
    x, _ = jax.lax.scan(functools.partial(_block, cfg=cfg), x, blocks)
    return x


def text_tower_traces() -> int:
    """Entries in :func:`text_stack`'s compile cache — one per (cfg, token
    shape) called eagerly; calls under an outer ``jit``/``grad`` add none."""
    return text_stack._cache_size()


def encode_text(p: Params, cfg: ModelConfig, tokens: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """tokens (B,L) int32 (byte+2; 257=EOS pad) -> (features (B,L,d), pooled (B,d))."""
    x = jnp.take(p["embed"], tokens, axis=0)
    x = text_stack(p["blocks"], cfg, x)
    x = rms_norm(x, p["ln_f"])
    # masked mean pool (pad id 257): far more separable than last-token
    # pooling on short templated prompts (EXPERIMENTS.md notes)
    not_pad = (tokens != 257).astype(jnp.float32)[..., None]
    pooled = jnp.sum(x * not_pad, axis=1) / jnp.maximum(
        jnp.sum(not_pad, axis=1), 1.0)
    pooled = pooled / jnp.linalg.norm(pooled, axis=-1, keepdims=True)
    return x, pooled


def init_image(key, dim: int = 256, patch: int = 8, image: int = 64,
               layers: int = 4) -> Params:
    n = (image // patch) ** 2
    cfg = text_cfg(dim, layers)
    ks = jax.random.split(key, 4)
    tower = init_text(ks[0], cfg)
    return {"cfg_dim": jnp.zeros((0,)),  # marker
            "patch_in": dense_init(ks[1], patch * patch * 3, dim),
            "pos": jax.random.normal(ks[2], (n, dim)) * 0.02,
            "blocks": tower["blocks"], "ln_f": tower["ln_f"]}


def encode_image(p: Params, images: jax.Array, dim: int = 256,
                 patch: int = 8, layers: int = 4) -> jax.Array:
    """images (B,H,W,3) in [-1,1] -> (B,d) L2-normalised."""
    B, H, W, C = images.shape
    cfg = text_cfg(dim, layers)
    x = images.reshape(B, H // patch, patch, W // patch, patch, C)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(B, -1, patch * patch * C)
    x = dot(x, p["patch_in"]) + p["pos"][None]

    x, _ = jax.lax.scan(functools.partial(_block, cfg=cfg, causal=False),
                        x, p["blocks"])
    pooled = jnp.mean(rms_norm(x, p["ln_f"]), axis=1)
    return pooled / jnp.linalg.norm(pooled, axis=-1, keepdims=True)


def contrastive_loss(text_p: Params, img_p: Params, cfg: ModelConfig,
                     tokens: jax.Array, images: jax.Array,
                     temp: float = 0.07) -> jax.Array:
    _, te = encode_text(text_p, cfg, tokens)
    ie = encode_image(img_p, images, dim=cfg.d_model, layers=cfg.n_layers)
    logits = te @ ie.T / temp
    labels = jnp.arange(tokens.shape[0])
    li = -jnp.mean(jax.nn.log_softmax(logits, axis=1)[labels, labels])
    lt = -jnp.mean(jax.nn.log_softmax(logits, axis=0)[labels, labels])
    return 0.5 * (li + lt)


def tokenize(prompts, max_len: int = 64) -> jnp.ndarray:
    """Byte tokenizer: bytes + BOS(256)/EOS+pad(257)."""
    import numpy as np
    out = np.full((len(prompts), max_len), 257, np.int32)
    for i, s in enumerate(prompts):
        bs = list(s.encode("utf-8"))[: max_len - 2]
        out[i, 0] = 256
        out[i, 1:1 + len(bs)] = bs
    return jnp.asarray(out)
