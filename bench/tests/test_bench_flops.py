"""The FLOP arithmetic and the weight draw against the program's own
parameter tree, at both published widths (shapes only: nothing runs)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench import flops, harness, weights

CONFIGS = ("sage-dit", "sage-dit-100m")


def _program_tree(spec):
    from repro.models import dit
    cfg = harness.model_config(spec)
    return cfg, jax.eval_shape(lambda k: dit.init_params(cfg, k),
                               jax.random.PRNGKey(0))


@pytest.mark.parametrize("name", CONFIGS)
def test_param_count_matches_program_tree(name):
    spec = harness.load_config(name)
    _, tree = _program_tree(spec)
    n = sum(x.size for x in jax.tree.leaves(tree))
    assert flops.param_count(spec) == n


@pytest.mark.parametrize("name", CONFIGS)
def test_bench_weights_have_the_program_layout(name):
    spec = harness.load_config(name)
    _, tree = _program_tree(spec)
    drawn = jax.eval_shape(lambda k: weights.dit_tree(spec, k),
                           jax.random.PRNGKey(0))
    assert jax.tree.structure(drawn) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(drawn), jax.tree.leaves(tree)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


@pytest.mark.parametrize("name", CONFIGS)
def test_row_eval_flops_match_xla_count_of_one_layer(name):
    """XLA's cost analysis counts a scanned layer once, so compare a
    one-layer model: the arithmetic may only leave out elementwise work
    (XLA counts it, so it reads a little higher)."""
    from repro.models import dit
    spec = dict(harness.load_config(name), n_layers=1)
    cfg = dataclasses.replace(harness.model_config(spec, exact=False))
    p = jax.eval_shape(lambda k: dit.init_params(cfg, k),
                       jax.random.PRNGKey(0))
    S = cfg.latent_size
    args = (jax.ShapeDtypeStruct((1, S, S, cfg.latent_channels),
                                 jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((1, cfg.cond_len, cfg.cond_dim),
                                 jnp.float32))
    xla = jax.jit(lambda p, z, t, c: dit.forward(p, cfg, z, t, c)).lower(
        p, *args).cost_analysis()["flops"]
    ours = flops.row_eval_flops(spec)
    assert ours <= xla <= 1.05 * ours
