"""Traffic: a pure function of the seed, the same work for every seed
(and one stream for every seed where the mix fixes the order), and prompt similarities on the fixed text towers that put each theme in
one group and no two themes together."""
import itertools
from collections import Counter

import numpy as np
import pytest

from bench import generator, harness, weights

SEEDS = (7, 2 ** 33 + 5)
MIXES = ("themed-batch", "distinct-batch")


def _traffic(mix, seed):
    return generator.Traffic(generator.load_mix(mix), {"clients": 8}, seed)


def _take(it, n):
    return list(itertools.islice(it, n))


@pytest.mark.parametrize("mix", MIXES)
def test_prompts_are_a_function_of_the_seed(mix):
    a = _take(_traffic(mix, SEEDS[1]).prompts(), 200)
    assert a == _take(_traffic(mix, SEEDS[1]).prompts(), 200)
    assert a != _take(_traffic(mix, SEEDS[0]).prompts(), 200)


def test_fixed_order_mix_is_one_stream_for_every_seed():
    a = _take(_traffic("themed-fixed-batch", SEEDS[0]).prompts(), 200)
    assert a == _take(_traffic("themed-fixed-batch", SEEDS[1]).prompts(), 200)
    assert a != _take(_traffic("themed-batch", SEEDS[0]).prompts(), 200)


def test_themed_blocks_hold_the_same_prompts_for_every_seed():
    n = generator.BLOCK
    a = _take(_traffic("themed-fixed-batch", SEEDS[0]).prompts(), 2 * n)
    b = _take(_traffic("themed-batch", SEEDS[1]).prompts(), 2 * n)
    assert Counter(a[:n]) == Counter(b[:n])
    assert Counter(a[n:]) == Counter(b[n:])


def test_zipf_counts():
    c = generator.zipf_counts(16, 1.0, 64)
    assert sum(c) == 64 and min(c) >= 1
    assert c == sorted(c, reverse=True) and c[0] == 19


def test_distinct_prompts_open_with_distinct_words():
    words = generator.load_prompt_set("words")["words"]
    p = _take(_traffic("distinct-batch", SEEDS[1]).prompts(), len(words))
    assert len({x.split()[0] for x in p}) == len(words)
    assert all(5 <= len(x.split()) <= 7 for x in p)


def _across_max(pooled, groups):
    """Largest cosine between members of different ``groups``."""
    sim = pooled @ pooled.T
    return max(float(sim[np.ix_(a, b)].max())
               for a, b in itertools.combinations(groups, 2))


@pytest.fixture(scope="module", params=("sage-dit", "sage-dit-100m"))
def pooled(request):
    """Pooled prompt embeddings of every theme variant and the warm-up
    prompts on the config's fixed text tower."""
    import jax
    from bench.references import dit as ref
    spec = harness.load_config(request.param)
    tp = jax.jit(lambda k: weights.text_tree(spec["text_tower"], k))(
        jax.random.PRNGKey(weights.TEXT_KEY))
    themes = generator.load_prompt_set("themes16")["themes"]
    prompts, groups = [], []
    for th in themes:
        v = generator.theme_prompts(th)
        assert max(len(x) for x in v) <= spec["cond_len"] - 2
        groups.append(list(range(len(prompts), len(prompts) + len(v))))
        prompts += v
    warm = generator.load_prompt_set("warmup")["prompts"]
    _, p = ref.embed(spec, tp, prompts + warm)
    return p[:len(prompts)], groups, p[len(prompts):]


def test_themes_group_within_and_not_across(pooled):
    p, groups, _ = pooled
    sim = p @ p.T
    within = min(sim[np.ix_(g, g)].min() for g in groups)
    assert within > 0.945          # tau_min 0.9, and trunk hits at 0.95
    assert _across_max(p, groups) < 0.895


def test_warmup_prompts_stay_apart(pooled):
    p, _, w = pooled
    s = w @ w.T
    np.fill_diagonal(s, -1)
    assert s.max() < 0.88
    assert (w @ p.T).max() < 0.9
