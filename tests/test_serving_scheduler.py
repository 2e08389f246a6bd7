"""Continuous-batching serving scheduler + cross-batch trunk cache.

Covers: segment-resume parity with one-shot shared_sample (both samplers,
both step_impl values, multiple slice sizes — the acceptance bar),
incremental grouping invariants, the (tau_min, tau_max] convention +
group_max guard, the oversize-clique completion-mapping regression,
per-group adaptive beta, TrunkCache LRU/byte accounting, and the
streaming-vs-sync NFE win on a repeated-theme arrival trace.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import SageConfig, get_config
from repro.core import grouping
from repro.core import shared_sampling as ss
from repro.core.schedule import make_schedule
from repro.data.synthetic import ShapesDataset
from repro.models import dit
from repro.models import text_encoder as te
from repro.serving.engine import SageServingEngine
from repro.serving.scheduler import RequestScheduler
from repro.serving.trunk_cache import TrunkCache, TrunkEntry

SCHED = make_schedule(1000)
CFG = get_config("sage-dit", smoke=True)
PARAMS = dit.init_params(CFG, jax.random.PRNGKey(0))
TC = te.text_cfg(dim=CFG.cond_dim, layers=2)
TEXT_PARAMS = te.init_text(jax.random.PRNGKey(1), TC)
H = CFG.latent_size
SHAPE = (H, H, CFG.latent_channels)


def _eps_fn(z, t, c):
    return dit.forward(PARAMS, CFG, z, t, c)


def _engine(sage, **kw):
    return SageServingEngine(CFG, sage, dit_params=PARAMS,
                             text_params=TEXT_PARAMS, text_cfg=TC, **kw)


# ---------------------------------------------------------------------------
# segment-resume parity (the tentpole refactor's contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sampler", ["ddim", "dpmpp"])
@pytest.mark.parametrize("step_impl", ["reference", "fused"])
@pytest.mark.parametrize("slice_steps", [1, 3])
def test_segment_resume_matches_one_shot(sampler, step_impl, slice_steps):
    """shared_phase/branch_phase slices of any size S must reproduce the
    one-shot shared_sample latents bitwise — including DPM-Solver++(2M),
    whose history carry crosses segment boundaries."""
    sage = SageConfig(total_steps=6, share_ratio=0.33, guidance_scale=3.0,
                      sampler=sampler, step_impl=step_impl)
    K, N = 2, 3
    cond = jax.random.normal(jax.random.PRNGKey(1),
                             (K, N, CFG.cond_len, CFG.cond_dim))
    mask = jnp.ones((K, N))
    null = jnp.zeros((CFG.cond_len, CFG.cond_dim))
    one = ss.shared_sample(_eps_fn, SCHED, sage, jax.random.PRNGKey(2),
                           cond, mask, null, SHAPE)

    T, Ts = sage.total_steps, sage.branch_point
    n_shared = T - Ts
    carry = ss.init_carry(jax.random.PRNGKey(2), K, SHAPE)
    cbar = ss.group_mean(cond, mask)
    done = 0
    while done < n_shared:
        s = min(slice_steps, n_shared - done)
        carry = ss.shared_phase(_eps_fn, SCHED, sage, carry, cbar, null, s)
        done += s
    assert int(carry.step_idx) == n_shared
    carry = ss.fork_carry(carry, N)
    cm = cond.reshape(K * N, CFG.cond_len, CFG.cond_dim)
    while done < T:
        s = min(slice_steps, T - done)
        carry = ss.branch_phase(_eps_fn, SCHED, sage, carry, cm, mask, null,
                                s, fork_idx=n_shared)
        done += s
    sliced = np.asarray(carry.z.reshape(K, N, *SHAPE))
    np.testing.assert_array_equal(sliced, np.asarray(one["latents"]))


def test_segment_nfe_helpers_match_one_shot():
    sage = SageConfig(total_steps=8, share_ratio=0.25)
    K, N = 2, 3
    mask = jnp.ones((K, N))
    n_shared = sage.total_steps - sage.branch_point
    nfe = (ss.shared_phase_nfe(K, n_shared)
           + float(ss.branch_phase_nfe(mask, sage.branch_point,
                                       sage.shared_uncond_cfg)))
    assert nfe == 2 * K * n_shared + 2 * K * N * sage.branch_point


def test_fork_carry_broadcasts_and_zeroes_history():
    carry = ss.SampleCarry(jnp.arange(2 * 4 * 4 * 3, dtype=jnp.float32
                                      ).reshape(2, 4, 4, 3),
                           jnp.ones((2, 4, 4, 3)), jnp.int32(5))
    forked = ss.fork_carry(carry, 3)
    assert forked.z.shape == (6, 4, 4, 3)
    assert int(forked.step_idx) == 5
    np.testing.assert_array_equal(np.asarray(forked.eps_prev), 0.0)
    np.testing.assert_array_equal(np.asarray(forked.z[0]),
                                  np.asarray(forked.z[2]))
    np.testing.assert_array_equal(np.asarray(forked.z[1]),
                                  np.asarray(carry.z[0]))


# ---------------------------------------------------------------------------
# grouping: tau convention, guards, incremental admission
# ---------------------------------------------------------------------------

def test_edge_mask_interval_convention():
    sim = np.array([0.3, 0.300001, 0.9, 0.95])
    m = grouping.edge_mask(sim, 0.3, 0.9)
    assert m.tolist() == [False, True, True, False]   # (tau_min, tau_max]
    with pytest.raises(ValueError):
        grouping.edge_mask(sim, 0.9, 0.9)             # empty interval


def test_greedy_clique_groups_group_max_guard():
    sim = np.eye(3)
    with pytest.raises(ValueError):
        grouping.greedy_clique_groups(sim, 0.5, group_max=0)
    with pytest.raises(ValueError):
        grouping.incremental_assign(np.ones(4), [], 0.5, group_max=0)


def test_incremental_assign_keeps_clique_invariant():
    """Arrival-order admission must satisfy the same pairwise-edge
    invariant greedy_clique_groups enforces."""
    rng = np.random.RandomState(0)
    tau, gmax = 0.3, 4
    embeds = rng.randn(30, 16)
    groups = []          # list of member index lists
    for i, e in enumerate(embeds):
        gi = grouping.incremental_assign(
            e, [embeds[g] for g in groups], tau, group_max=gmax)
        if gi >= 0:
            groups[gi].append(i)
        else:
            groups.append([i])
    flat = sorted(i for g in groups for i in g)
    assert flat == list(range(30))
    sim = grouping.similarity_matrix(embeds)
    for g in groups:
        assert 1 <= len(g) <= gmax
        for i in g:
            for j in g:
                if i != j:
                    assert sim[i, j] > tau


def test_incremental_assign_prefers_tightest_and_skips_full():
    a = np.array([1.0, 0.0, 0.0, 0.0])
    b = np.array([0.92, 0.39, 0.0, 0.0])    # cos(a,b) ~ 0.92
    new = np.array([0.99, 0.14, 0.0, 0.0])
    # two open groups: [a] (tighter for new) and [b]
    gi = grouping.incremental_assign(new, [np.stack([a]), np.stack([b])],
                                     0.5)
    assert gi == 0
    # group 0 full -> falls to group 1
    gi = grouping.incremental_assign(new, [np.stack([a] * 2), np.stack([b])],
                                     0.5, group_max=2)
    assert gi == 1
    # nothing admissible -> seed new
    gi = grouping.incremental_assign(new, [np.stack([-a])], 0.5)
    assert gi == -1


def test_flatten_groups_matches_pad_rows():
    groups = [[0, 1, 2, 3, 4, 5, 6], [7, 8]]
    flat = grouping.flatten_groups(groups, 4)
    idx, mask = grouping.pad_groups(groups, 4)
    assert flat == [[0, 1, 2, 3], [4, 5, 6], [7, 8]]
    for k, row in enumerate(flat):
        assert idx[k, :len(row)].tolist() == row
        assert mask[k].sum() == len(row)


# ---------------------------------------------------------------------------
# engine regressions: oversize-clique completion mapping, per-group beta
# ---------------------------------------------------------------------------

def test_oversize_clique_completion_mapping():
    """7-member clique packed at group_size=4 splits over two rows; every
    prompt must come back exactly once (the old engine iterated the
    *unsplit* groups and dropped/misaligned the tail rows)."""
    sage = SageConfig(total_steps=4, share_ratio=0.25, guidance_scale=2.0,
                      tau_min=0.05)
    sched = RequestScheduler(CFG, sage, PARAMS, TEXT_PARAMS, TC,
                             group_size=4, group_max=7)
    base = "a small red circle on a blue background"
    prompts = [base] * 7
    done = sched.run_batch(prompts)
    assert len(done) == 7
    assert sorted(c.prompt for c in done) == sorted(prompts)
    assert len({c.group_id for c in done}) == 2       # 4 + 3 packed rows
    assert sched.stats["completed"] == 7


def test_adaptive_beta_is_per_group():
    """A singleton group (min-sim pinned to 1.0) must not drag other
    groups' beta bucket: NFE must equal the per-group-bucket sum."""
    sage = SageConfig(total_steps=10, share_ratio=0.3, guidance_scale=2.0,
                      tau_min=0.5, adaptive_branch=True)
    sched = RequestScheduler(CFG, sage, PARAMS, TEXT_PARAMS, TC,
                             group_size=4, branch_buckets=(0.2, 0.3, 0.4))
    # controlled similarity space: a pair at cos=0.6 and an unrelated
    # singleton
    pooled = np.array([[1.0, 0.0], [0.6, 0.8], [0.0, -1.0]], np.float32)
    conds = np.asarray(jax.random.normal(
        jax.random.PRNGKey(3), (3, CFG.cond_len, CFG.cond_dim)))
    sched._embed = lambda prompts: (conds[:len(prompts)],
                                    pooled[:len(prompts)])
    done = sched.run_batch(["p0", "p1", "p2"], adaptive=True)
    assert len(done) == 3
    # groups: {0,1} (cos 0.6 -> beta_raw 0.3 -> bucket 0.3, Ts=7) and {2}
    # (singleton -> beta_raw 0.5 -> bucket 0.4, Ts=6)
    expect = (2 * 1 * 3 + 2 * 2 * 7) + (2 * 1 * 4 + 2 * 1 * 6)
    assert sched.stats["nfe"] == expect
    # the old batch-mean bucket (mean(0.6, 1.0)*0.5 -> 0.4 for BOTH groups)
    # would have produced a different total
    old = (2 * 1 * 4 + 2 * 2 * 6) + (2 * 1 * 4 + 2 * 1 * 6)
    assert expect != old


# ---------------------------------------------------------------------------
# trunk cache
# ---------------------------------------------------------------------------

def _entry(centroid, beta=0.3, cfg_key=("k",), shape=(1, 4, 4, 3), fill=0.0):
    z = np.full(shape, fill, np.float32)
    return TrunkEntry(z=z, eps_prev=np.zeros_like(z), step_idx=2,
                      beta_bucket=beta, rng_fold=0,
                      centroid=np.asarray(centroid, np.float32),
                      cfg_key=cfg_key)


def test_trunk_cache_exact_and_cosine_hits():
    c = TrunkCache(tau_trunk=0.9)
    e = _entry([1.0, 0.0, 0.0])
    c.insert(e, shape=(1, 4, 4, 3))
    # exact quantized-key hit
    hit = c.lookup([1.0, 0.0, 0.0], 0.3, ("k",), (1, 4, 4, 3))
    assert hit is e and c.stats["exact_hits"] == 1
    # near-duplicate cosine hit (rounded key differs)
    hit = c.lookup([0.98, 0.199, 0.0], 0.3, ("k",), (1, 4, 4, 3))
    assert hit is e
    # below tau_trunk -> miss
    assert c.lookup([0.0, 1.0, 0.0], 0.3, ("k",), (1, 4, 4, 3)) is None
    # bucket / cfg / shape mismatches -> miss even at cosine 1.0
    assert c.lookup([1.0, 0.0, 0.0], 0.2, ("k",), (1, 4, 4, 3)) is None
    assert c.lookup([1.0, 0.0, 0.0], 0.3, ("other",), (1, 4, 4, 3)) is None
    assert c.lookup([1.0, 0.0, 0.0], 0.3, ("k",), (1, 8, 8, 3)) is None


def test_trunk_cache_lru_byte_budget():
    shape = (1, 4, 4, 3)
    nbytes = int(np.prod(shape)) * 4 * 2              # z + eps_prev
    cache = TrunkCache(tau_trunk=0.99, max_bytes=3 * nbytes)
    dirs = np.eye(4, 8)
    for i in range(3):
        cache.insert(_entry(dirs[i], fill=float(i)), shape=shape)
    assert len(cache) == 3 and cache.bytes == 3 * nbytes
    # touch entry 0 -> entry 1 becomes LRU
    assert cache.lookup(dirs[0], 0.3, ("k",), shape) is not None
    cache.insert(_entry(dirs[3]), shape=shape)
    assert len(cache) == 3
    assert cache.stats["evictions"] == 1
    assert cache.lookup(dirs[1], 0.3, ("k",), shape) is None   # evicted
    assert cache.lookup(dirs[0], 0.3, ("k",), shape) is not None
    # replacing the same key does not double-count bytes
    cache.insert(_entry(dirs[0], fill=9.0), shape=shape)
    assert cache.bytes == 3 * nbytes


def test_trunk_cache_overwrite_byte_accounting():
    """Overwriting an existing exact key must be evict-then-insert: the
    ledger ``bytes`` always equals the recount over stored entries — no
    double-count, under budget pressure, across store_history modes, and
    for same-object re-inserts."""
    shape = (1, 4, 4, 3)
    nbytes = int(np.prod(shape)) * 4 * 2
    c = TrunkCache(tau_trunk=0.9, max_bytes=2 * nbytes)
    c.insert(_entry([1.0, 0.0], fill=1.0), shape=shape)
    for fill in (2.0, 3.0, 4.0):              # repeated same-key overwrite
        c.insert(_entry([1.0, 0.0], fill=fill), shape=shape)
        assert c.bytes == c.ledger_bytes() == nbytes
    assert len(c) == 1 and c.stats["overwrites"] == 3
    assert c.stats["evictions"] == 0          # overwrite is not an eviction
    # overwrite while a second entry sits at the budget edge
    c.insert(_entry([0.0, 1.0], fill=5.0), shape=shape)
    c.insert(_entry([1.0, 0.0], fill=6.0), shape=shape)
    assert len(c) == 2 and c.bytes == c.ledger_bytes() == 2 * nbytes
    # same-object re-insert must not double-count either
    e = _entry([0.7071, 0.7071], fill=7.0)
    slim = TrunkCache(tau_trunk=0.9, store_history=False)
    slim.insert(e, shape=shape)
    slim.insert(e, shape=shape)
    assert len(slim) == 1
    assert slim.bytes == slim.ledger_bytes() == nbytes // 2


def test_trunk_cache_overwrite_fuzz_ledger():
    """Randomized insert/lookup/overwrite sequence: the incremental byte
    ledger must track the recount exactly at every step."""
    rng = np.random.RandomState(0)
    dirs = rng.randn(6, 8)
    for store_history in (True, False):
        c = TrunkCache(tau_trunk=0.9, max_bytes=5 * 384,
                       store_history=store_history)
        for step in range(200):
            d = dirs[rng.randint(6)]
            if rng.rand() < 0.7:
                c.insert(_entry(d, fill=float(step)), shape=(1, 4, 4, 3))
            else:
                c.lookup(d, 0.3, ("k",), (1, 4, 4, 3))
            assert c.bytes == c.ledger_bytes(), (step, store_history)
        assert (c.stats["inserts"]
                == len(c) + c.stats["evictions"] + c.stats["overwrites"])


def test_trunk_cache_validates_tau():
    with pytest.raises(ValueError):
        TrunkCache(tau_trunk=0.0)


def test_trunk_cache_exact_key_still_enforces_tau():
    """Coarse quantization can collide centroids whose true cosine is
    below tau_trunk; the exact-key fast path must not bypass the check."""
    c = TrunkCache(tau_trunk=0.95, quant_decimals=0)
    c.insert(_entry([0.9, 0.436]), shape=(1, 4, 4, 3))
    # [1, 0] quantizes to the same key but cos ~ 0.9 < 0.95 -> miss
    assert c.lookup([1.0, 0.0], 0.3, ("k",), (1, 4, 4, 3)) is None
    assert c.lookup([0.9, 0.436], 0.3, ("k",), (1, 4, 4, 3)) is not None


@pytest.mark.parametrize("index", ["scan", "lsh"])
def test_trunk_cache_collision_falls_through_to_similarity(index):
    """Directed regression: a quantized-key collision whose resident
    entry fails the cosine re-check must fall through to the similarity
    search, not return a miss — the colliding entry cannot be allowed to
    mask a compatible near-duplicate stored under a different key."""
    shape = (1, 4, 4, 3)
    c = TrunkCache(tau_trunk=0.95, quant_decimals=0, index=index)
    # stored under quant key (1, 0): cos to the query ~ 0.958 >= tau
    c.insert(_entry([0.970, 0.242], fill=1.0), shape=shape)
    # stored under quant key (1, 1): cos to the query ~ 0.88 < tau
    c.insert(_entry([0.515, 0.857], fill=2.0), shape=shape)
    assert len(c) == 2
    # query quantizes to (1, 1) -> exact-key path finds the *far* entry,
    # fails the re-check, and must still locate the near one by search
    hit = c.lookup([0.86, 0.51], 0.3, ("k",), shape)
    assert hit is not None, "collision masked a compatible near-duplicate"
    assert float(np.asarray(hit.z).ravel()[0]) == 1.0
    assert c.stats["hits"] == 1 and c.stats["exact_hits"] == 0


def test_trunk_cache_payload_namespaces():
    """ar_prefix and diffusion-trunk payloads share the cache but can
    never satisfy each other's lookups, even with identical centroids."""
    shape = (1, 4, 4, 3)
    c = TrunkCache(tau_trunk=0.9)
    e = _entry([1.0, 0.0])
    e.payload = "ar_prefix"
    c.insert(e, shape=shape)
    assert c.lookup([1.0, 0.0], 0.3, ("k",), shape,
                    payload="trunk") is None
    assert c.lookup([1.0, 0.0], 0.3, ("k",), shape,
                    payload="ar_prefix") is not None


def test_trunk_cache_tier_spill_and_promote():
    """HBM overflow spills LRU entries to the host tier (bytes conserved
    across the move), and a host hit promotes back to HBM — with the
    per-tier ledgers balancing throughout."""
    shape = (1, 4, 4, 3)
    per = 2 * int(np.prod(shape)) * 4            # z + eps_prev
    dirs = np.eye(4, dtype=np.float32)
    c = TrunkCache(tau_trunk=0.9, max_bytes=2 * per,
                   host_bytes=10 * per)
    for i in range(4):
        c.insert(_entry(dirs[i], fill=float(i)), shape=shape)
    # 4 inserts into a 2-entry HBM budget: two spills, nothing evicted
    assert len(c) == 4 and c.stats["spills"] == 2
    assert c.stats["evictions"] == 0
    assert c.tier_bytes == {"hbm": 2 * per, "host": 2 * per}
    assert c.tier_bytes == c.tier_ledger()
    assert c.bytes == c.ledger_bytes() == 4 * per
    # entry 0 spilled first (LRU); a hit on it promotes it back,
    # displacing the coldest HBM resident
    hit = c.lookup(dirs[0], 0.3, ("k",), shape)
    assert hit is not None and hit.tier == "hbm"
    assert c.stats["promotions"] == 1 and c.stats["spills"] == 3
    assert c.tier_bytes == c.tier_ledger()
    assert c.bytes == c.ledger_bytes() == 4 * per
    # promoted payloads come back as device arrays
    import jax
    assert isinstance(hit.z, jax.Array)


def test_trunk_cache_host_budget_evicts_for_real():
    """Host-tier overflow is terminal: the spill tier's own budget
    evicts, and with host_bytes=0 HBM overflow evicts directly (the
    pre-tier behavior)."""
    shape = (1, 4, 4, 3)
    per = 2 * int(np.prod(shape)) * 4
    dirs = np.eye(6, dtype=np.float32)
    c = TrunkCache(tau_trunk=0.9, max_bytes=2 * per, host_bytes=1 * per)
    for i in range(6):
        c.insert(_entry(dirs[i], fill=float(i)), shape=shape)
    assert len(c) == 3                           # 2 hbm + 1 host
    assert c.stats["spills"] == 4 and c.stats["evictions"] == 3
    assert c.tier_bytes == c.tier_ledger() == {"hbm": 2 * per,
                                               "host": 1 * per}
    flat = TrunkCache(tau_trunk=0.9, max_bytes=2 * per)   # host disabled
    for i in range(6):
        flat.insert(_entry(dirs[i], fill=float(i)), shape=shape)
    assert len(flat) == 2 and flat.stats["spills"] == 0
    assert flat.stats["evictions"] == 4
    assert flat.tier_bytes == {"hbm": 2 * per, "host": 0}


def test_trunk_cache_store_history_flag_halves_bytes():
    shape = (1, 4, 4, 3)
    z_bytes = int(np.prod(shape)) * 4
    full = TrunkCache(tau_trunk=0.9)
    full.insert(_entry([1.0, 0.0]), shape=shape)
    slim = TrunkCache(tau_trunk=0.9, store_history=False)
    slim.insert(_entry([1.0, 0.0]), shape=shape)
    assert full.bytes == 2 * z_bytes and slim.bytes == z_bytes
    hit = slim.lookup([1.0, 0.0], 0.3, ("k",), shape)
    assert hit is not None and hit.eps_prev is None


# ---------------------------------------------------------------------------
# streaming scheduler end-to-end
# ---------------------------------------------------------------------------

def _wave_prompts(n=3):
    _, prompts = ShapesDataset(res=16).batch(0, n)
    return prompts


def test_streaming_singleton_launches_after_max_wait():
    sage = SageConfig(total_steps=4, share_ratio=0.25, guidance_scale=2.0,
                      tau_min=0.2)
    sched = RequestScheduler(CFG, sage, PARAMS, TEXT_PARAMS, TC,
                             group_size=4, slice_steps=2, max_wait_ticks=2)
    sched.submit(_wave_prompts(1), now=0.0)
    assert sched.tick(now=1.0) == []                   # waiting for peers
    assert sched.open_groups and not sched.inflight
    done = []
    t = 1.0
    while sched.pending:
        t += 1.0
        done.extend(sched.tick(now=t))
    assert len(done) == 1
    assert done[0].latency > 0
    s = sched.summary()
    assert s["completed"] == 1 and s["latency_p50"] > 0


def test_single_prompt_submits_compile_text_stack_once():
    sage = SageConfig(total_steps=4, share_ratio=0.25, guidance_scale=2.0,
                      tau_min=0.2)
    sched = RequestScheduler(CFG, sage, PARAMS, TEXT_PARAMS, TC,
                             group_size=4, slice_steps=2)
    te.text_stack.clear_cache()
    prompts = _wave_prompts(20)
    for i, p in enumerate(prompts):
        sched.submit([p], now=float(i))
    assert sched.metrics.snapshot()["text_tower_traces"] == 1
    sched.submit(prompts[:3], now=20.0)
    assert sched.metrics.snapshot()["text_tower_traces"] == 2
    assert sched.stats["requests"] == 23


def test_streaming_deadline_forces_launch():
    """An approaching (still meetable) deadline launches a sub-full group
    ahead of ``max_wait_ticks``.  An *already-expired* deadline no longer
    reaches this path at all — it is refused at admission with
    ``status='rejected_expired'`` (see tests/test_qos.py)."""
    sage = SageConfig(total_steps=4, share_ratio=0.25, guidance_scale=2.0,
                      tau_min=0.2)
    sched = RequestScheduler(CFG, sage, PARAMS, TEXT_PARAMS, TC,
                             group_size=4, slice_steps=4, max_wait_ticks=50)
    sched.submit(_wave_prompts(1), now=0.0, deadline=3.0)
    sched.tick(now=1.0)                                # deadline far: held
    assert sched.open_groups and not sched.inflight
    sched.tick(now=2.0)
    sched.tick(now=3.0)                                # deadline reached ->
    assert not sched.open_groups and sched.inflight    # launched despite
    #                                                   being 1/4 full


def test_streaming_cache_beats_sync_on_repeated_theme():
    """Acceptance: on a repeated-theme arrival trace the trunk-cache path
    must spend strictly fewer NFE than the synchronous engine serving the
    same waves, and the saving must show up in the stats."""
    sage = SageConfig(total_steps=6, share_ratio=0.33, guidance_scale=2.0,
                      tau_min=0.2)
    prompts = _wave_prompts(3)
    waves = 3

    sync = _engine(sage, group_size=3)
    for _ in range(waves):                             # arrivals over time:
        sync.submit(prompts)                           # one batch per wave
        sync.step(max_batch=len(prompts))
    nfe_sync = sync.stats["nfe"]

    stream = _engine(sage, group_size=3).streaming_scheduler(
        slice_steps=2, max_wait_ticks=1, trunk_cache=TrunkCache(
            tau_trunk=0.9))
    t, done = 0.0, []
    for _ in range(waves):
        stream.submit(prompts, now=t)
        while stream.pending:
            t += 1.0
            done.extend(stream.tick(now=t))
    assert len(done) == waves * len(prompts)
    assert stream.stats["nfe"] < nfe_sync              # strict NFE win
    assert stream.stats["nfe_saved_cache"] > 0
    assert stream.trunk_cache.stats["hits"] >= waves - 1
    assert any(c.cache_hit for c in done)
    assert all(np.isfinite(c.image).all() for c in done)
    # NFE accounting closes: sync spend == stream spend + cached savings
    assert nfe_sync == stream.stats["nfe"] + stream.stats["nfe_saved_cache"]


def test_streaming_matches_sync_nfe_without_cache():
    """No cache, arrivals in one burst: the tick loop is the synchronous
    path run in slices — identical grouping, identical NFE."""
    sage = SageConfig(total_steps=6, share_ratio=0.33, guidance_scale=2.0,
                      tau_min=0.2)
    prompts = _wave_prompts(4)

    sync = _engine(sage, group_size=4)
    sync.submit(prompts)
    sync.step(max_batch=len(prompts))

    stream = _engine(sage, group_size=4).streaming_scheduler(
        slice_steps=2, max_wait_ticks=1)
    stream.submit(prompts, now=0.0)
    done = []
    t = 0.0
    while stream.pending:
        t += 1.0
        done.extend(stream.tick(now=t))
    assert len(done) == len(prompts)
    assert stream.stats["nfe"] == sync.stats["nfe"]
    assert stream.stats["nfe_independent"] == sync.stats["nfe_independent"]
