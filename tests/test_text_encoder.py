"""Text tower: the layer stack is compiled once per (cfg, token shape) and
reused, and the compiled stack keeps the numerics of a plain eager scan.

The reference below builds the scan around a fresh closure on every call,
as an eager tower would; ``encode_text`` must match it bitwise at these
widths, and to rounding under an outer ``jit`` and under ``grad``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as attn
from repro.models import text_encoder as te
from repro.models.layers import apply_mlp, rms_norm

PROMPTS = ["a red circle on a blue field", "two green squares",
           "a small yellow triangle", "stripes"]


def _eager_encode(p, cfg, tokens):
    x = jnp.take(p["embed"], tokens, axis=0)

    def body(x, bp):
        x = x + attn.gqa_full(bp["attn"], cfg, rms_norm(x, bp["ln1"]))
        x = x + apply_mlp(bp["mlp"], rms_norm(x, bp["ln2"]), cfg.mlp_kind)
        return x, None

    x, _ = jax.lax.scan(body, x, p["blocks"])
    x = rms_norm(x, p["ln_f"])
    not_pad = (tokens != 257).astype(jnp.float32)[..., None]
    pooled = jnp.sum(x * not_pad, axis=1) / jnp.maximum(
        jnp.sum(not_pad, axis=1), 1.0)
    return x, pooled / jnp.linalg.norm(pooled, axis=-1, keepdims=True)


def _tower(dim):
    cfg = te.text_cfg(dim=dim, layers=2)
    return cfg, te.init_text(jax.random.PRNGKey(dim), cfg)


def test_stack_traces_once_per_token_shape():
    cfg, p = _tower(32)
    te.text_stack.clear_cache()
    one = te.tokenize(PROMPTS[:1])
    te.encode_text(p, cfg, one)
    te.encode_text(p, cfg, te.tokenize(PROMPTS[1:2]))
    assert te.text_tower_traces() == 1
    te.encode_text(p, cfg, te.tokenize(PROMPTS[:2]))   # new batch size
    assert te.text_tower_traces() == 2
    te.encode_text(p, cfg, one)
    assert te.text_tower_traces() == 2


@pytest.mark.parametrize("dim", [32, 64])
@pytest.mark.parametrize("batch", [1, 2])
def test_encode_text_bitwise_matches_eager_scan(dim, batch):
    cfg, p = _tower(dim)
    toks = te.tokenize(PROMPTS[:batch])
    got = te.encode_text(p, cfg, toks)
    want = _eager_encode(p, cfg, toks)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_encode_text_under_outer_jit_matches_eager_scan():
    cfg, p = _tower(64)
    toks = te.tokenize(PROMPTS[:3])
    got = jax.jit(te.encode_text, static_argnums=1)(p, cfg, toks)
    want = _eager_encode(p, cfg, toks)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


def test_contrastive_loss_grad_matches_eager_scan(monkeypatch):
    cfg, tp = _tower(32)
    ip = te.init_image(jax.random.PRNGKey(7), dim=32, image=16, layers=2)
    toks = te.tokenize(PROMPTS)
    images = jax.random.uniform(jax.random.PRNGKey(8), (4, 16, 16, 3),
                                minval=-1.0, maxval=1.0)

    def loss_grad():   # a fresh jit, traced with the current encode_text
        return jax.jit(jax.value_and_grad(te.contrastive_loss,
                                          argnums=(0, 1)),
                       static_argnums=2)(tp, ip, cfg, toks, images)

    got_l, got_g = loss_grad()
    monkeypatch.setattr(te, "encode_text", _eager_encode)
    want_l, want_g = loss_grad()
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-6)
    for g, w in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-6)
