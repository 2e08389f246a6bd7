"""Serve sage-dit at its published widths on one TPU chip, through the
streaming scheduler with the compiled Pallas kernels, and check the
images against the per-group oracle.

    python chip_smoke.py [--seed N]

Phases, all in this one process (a chip belongs to one process):

1. build the engine: ``sage-dit`` (28 layers, d_model 1152, 64x64x4
   latents = 1,024 tokens, 77x768 text conditioning), random weights
   from ``--seed``;
2. served path: ``SageServingEngine.streaming_scheduler`` with packed
   ticks, ``attn_impl="pallas"`` and ``step_impl="fused"``; two waves of
   8 prompts in 2 themes (group size 4, T=30, guidance 7.5) behind a
   ``TrunkCache`` — wave 1 stores the shared trunks, wave 2 forks from
   them (similarity hits);
3. oracle: the same waves through the per-group path (``packed=False``,
   ``step_impl="reference"``, ``attn_impl="naive"``);
4. checks: every image finite, equal NFE ledgers and cache hits,
   per-image relative L2 of served vs oracle within ``REL_L2_BOUND``,
   and one denoiser evaluation with the flash kernel within
   ``EPS_REL_L2_BOUND`` of the naive one.

Exits non-zero, without the result line, when JAX finds no TPU, when
Pallas would run in interpret mode, when a kernel dispatch fell back to
another implementation, or when any phase or check fails.  The last line
of a passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

#: Largest per-image relative L2 distance (served vs oracle) accepted.
#: Both paths compute the denoiser in bf16; they differ in the attention
#: kernel (flash online softmax vs materialised scores) and in the solver
#: update (fused kernel vs jnp), so one evaluation differs at the bf16
#: rounding level, and 30 guided steps over random weights amplify that
#: ~20x.  CPU rehearsal at 2-6 layers (interpret mode): 0.07-0.10 with
#: the kernels as they are, 1.0-1.2 with the flash kernel's padded-key
#: mask removed.  Fixed before the first chip run; never tuned after it.
REL_L2_BOUND = 0.5
#: Largest per-row relative L2 of ONE denoiser evaluation, flash vs
#: naive attention (same rehearsal: 3.8e-3-4.7e-3 as they are, 0.26-0.28
#: with the mask removed).  Fixed with REL_L2_BOUND.
EPS_REL_L2_BOUND = 0.05

TOTAL_STEPS = 30            # paper setting (SageConfig defaults)
GUIDANCE = 7.5
GROUP_SIZE = 4

#: two waves x two themes x four prompts; wave 2 rephrases wave 1, so
#: its groups' centroids land within tau_trunk of the stored trunks
WAVES = (
    ("a red fox sleeping in fresh snow at dawn",
     "a red fox sleeping in deep snow at dusk",
     "a red fox resting in fresh snow at dawn",
     "a red fox sleeping on fresh snow at noon",
     "a neon city street at night in the rain",
     "a neon city street at night in the fog",
     "a neon city alley at night in the rain",
     "a neon city street at night in the snow"),
    ("a red fox sleeping in soft snow at dawn",
     "a red fox napping in fresh snow at dusk",
     "a red fox resting in deep snow at dawn",
     "a red fox sleeping in fresh snow at noon",
     "a neon city street at night in the mist",
     "a neon city alley at night in the fog",
     "a neon city street at dusk in the rain",
     "a neon city alley at night in the snow"),
)


def fail(msg: str) -> None:
    sys.exit(f"chip_smoke: FAIL: {msg}")


def sage_config():
    from repro.config import SageConfig
    # the random text tower puts within-theme prompts above 0.96 cosine
    # and across-theme pairs below 0.88: group on (0.9, 1.0]
    return SageConfig(total_steps=TOTAL_STEPS, guidance_scale=GUIDANCE,
                      tau_min=0.9, tau_max=1.0)


def smoke_params(cfg, seed: int):
    """Random DiT weights with every block reaching the output.

    ``dit.init_params`` is adaLN-zero (DiT's recipe): every attention
    and MLP block is gated by exactly 0, so a broken attention kernel
    would not change the images.  Here the adaLN, ``lnx`` and final
    adaLN leaves are drawn as small normals instead, and the output
    projection at 0.3x the fan-in scale (``init_params`` uses 0.02x):
    large enough that the network, not the initial noise, decides the
    images, small enough that the guided trajectory does not amplify
    bf16 rounding into unrelated images."""
    import jax

    from repro.models import dit
    from repro.models.layers import dense_init

    params = dit.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 2), 8))

    def small(a):
        return 0.02 * jax.random.normal(next(keys), a.shape, a.dtype)

    blocks = dict(params["blocks"])
    for name in ("adaln", "adaln_b", "lnx"):
        blocks[name] = small(blocks[name])
    params = dict(params, blocks=blocks)
    for name in ("final_adaln", "final_adaln_b"):
        params[name] = small(params[name])
    d, p_out = params["out"].shape
    params["out"] = 0.3 * dense_init(next(keys), d, p_out)
    return params


def serve(engine, *, packed: bool):
    """Both waves through one streaming scheduler with a trunk cache.
    Returns (completions keyed by (wave, prompt), scheduler, seconds)."""
    from repro.serving.trunk_cache import TrunkCache

    sched = engine.streaming_scheduler(trunk_cache=TrunkCache(),
                                       packed=packed)
    done = {}
    t0 = time.perf_counter()
    for w, prompts in enumerate(WAVES):
        sched.submit(list(prompts))
        while sched.pending:
            for c in sched.tick():
                done[(w, c.prompt)] = c
    return done, sched, time.perf_counter() - t0


def eps_rel_l2(cfg, params, engine):
    """Per-row relative L2 of one denoiser evaluation, flash kernel vs
    naive attention, on 4 rows of wave-1 prompts at spread timesteps."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.config import replace
    from repro.models import dit

    conds, _ = engine.scheduler._embed(list(WAVES[0][:4]))
    z = jax.random.normal(jax.random.PRNGKey(7),
                          (4, cfg.latent_size, cfg.latent_size,
                           cfg.latent_channels))
    t = jnp.array([999, 700, 400, 100], jnp.int32)
    eps = {}
    for impl in ("pallas", "naive"):
        c = replace(cfg, attn_impl=impl)
        fwd = jax.jit(lambda p, z, t, x, c=c: dit.forward(p, c, z, t, x))
        eps[impl] = np.asarray(fwd(params, z, t, jnp.asarray(conds)),
                               np.float64)
    rows = [(a - b, b) for a, b in zip(eps["pallas"], eps["naive"])]
    return max(float(np.linalg.norm(d) / np.linalg.norm(b)) for d, b in rows)


def run(cfg, seed: int, log=functools.partial(print, flush=True)):
    """Phases 1-4 for model config ``cfg``; returns the result record and
    raises on a failed check."""
    import jax
    import numpy as np

    from repro.kernels.dispatch import DISPATCH_LOG
    from repro.serving.engine import build_engine

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append((kw.get("fun_name"), secs))
        if event == "/jax/core/compile/backend_compile_duration" else None)

    sage = sage_config()
    t = time.perf_counter()
    # one program: drawn op by op, the 0.8 B leaves cost a minute of
    # small compiles
    params = jax.block_until_ready(
        jax.jit(smoke_params, static_argnums=(0, 1))(cfg, seed))
    n = sum(a.size for a in jax.tree.leaves(params))
    log(f"params             = {n / 1e9:.3f} B "
        f"({time.perf_counter() - t:.1f} s to draw)")
    DISPATCH_LOG.enabled = True

    runs = {}
    for name, packed, attn, step in (("served", True, "pallas", "fused"),
                                     ("oracle", False, "naive",
                                      "reference")):
        engine = build_engine(cfg, sage, seed=seed, dit_params=params,
                              group_size=GROUP_SIZE, attn_impl=attn,
                              step_impl=step)
        n0 = len(compiles)
        done, sched, secs = serve(engine, packed=packed)
        s = sched.summary()
        seg = [(f, c) for f, c in compiles[n0:]
               if f in ("jit(shared_segment)", "jit(branch_segment)")]
        log(f"{name:<7} compile s  = "
            + ", ".join(f"{f} {c:.1f}" for f, c in seg)
            + f" (all compiles {sum(c for _, c in compiles[n0:]):.1f})")
        log(f"{name:<7} window     = {secs:.2f} s wall, "
            f"{len(done)} completions, NFE {s['nfe']:.0f}, "
            f"cache hits {s['cache_hits']:.0f}, "
            f"launches {s['launches']:.0f}")
        runs[name] = (done, s, secs, seg)

    (served, s_srv, secs, seg), (oracle, s_orc, _, _) = (runs["served"],
                                                         runs["oracle"])
    eps_rel = eps_rel_l2(cfg, params, engine)
    log(f"eps rel L2         = max {eps_rel:.3e} over 4 rows "
        f"(bound {EPS_REL_L2_BOUND})")
    if not eps_rel <= EPS_REL_L2_BOUND:
        raise RuntimeError(f"flash attention off naive: eps rel L2 "
                           f"{eps_rel:.3e} > {EPS_REL_L2_BOUND}")
    if DISPATCH_LOG.fallbacks():
        raise RuntimeError(f"kernel dispatch fell back: "
                           f"{DISPATCH_LOG.fallbacks()}")
    chosen = {(r["op"], r["chosen"]) for r in DISPATCH_LOG.snapshot()}
    for need in (("attention", "pallas"), ("cfg_ddim_step", "fused")):
        if need not in chosen:
            raise RuntimeError(f"served path never dispatched {need}")

    n_req = sum(len(w) for w in WAVES)
    if len(served) != n_req or set(served) != set(oracle):
        raise RuntimeError(f"completions differ: served {len(served)}, "
                           f"oracle {len(oracle)}, want {n_req}")
    if s_srv["nfe"] != s_orc["nfe"]:
        raise RuntimeError(f"NFE ledgers differ: served {s_srv['nfe']}, "
                           f"oracle {s_orc['nfe']}")
    if s_srv["cache_hits"] < 1 or s_srv["cache_hits"] != s_orc["cache_hits"]:
        raise RuntimeError(f"cache hits: served {s_srv['cache_hits']}, "
                           f"oracle {s_orc['cache_hits']} (want equal, >0)")
    rel = {}
    for key, c in served.items():
        a = np.asarray(c.image, np.float64)
        b = np.asarray(oracle[key].image, np.float64)
        if not (np.isfinite(a).all() and np.isfinite(b).all()):
            raise RuntimeError(f"non-finite image for {key}")
        if c.nfe_share != oracle[key].nfe_share:
            raise RuntimeError(f"per-request NFE differs for {key}")
        rel[key] = float(np.linalg.norm(a - b) / np.linalg.norm(b))
    worst = max(rel.values())
    log(f"oracle rel L2      = max {worst:.3e}, mean "
        f"{np.mean(list(rel.values())):.3e} (bound {REL_L2_BOUND})")
    if not worst <= REL_L2_BOUND:
        raise RuntimeError(f"served images off the oracle: rel L2 "
                           f"{worst:.3e} > {REL_L2_BOUND}")
    return {"served_s": secs, "compile_s": [c for _, c in seg],
            "completions": len(served), "nfe": s_srv["nfe"],
            "cache_hits": s_srv["cache_hits"], "rel_l2_max": worst,
            "eps_rel_l2_max": eps_rel}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights")
    args = ap.parse_args()

    if os.environ.get("REPRO_KERNEL_INTERPRET"):
        fail("REPRO_KERNEL_INTERPRET is set; the chip run compiles kernels")
    import jax
    if jax.default_backend() != "tpu":
        fail(f"no TPU: JAX backend is {jax.default_backend()!r}")

    from repro.config import get_config
    from repro.kernels.dispatch import resolve_interpret
    from repro.launch.compile_cache import enable_compile_cache

    if resolve_interpret("auto"):
        fail("Pallas would run in interpret mode")
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    print(f"device             = {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}, compile cache {cache_dir}", flush=True)

    res = run(get_config("sage-dit"), args.seed)
    peak = dev.memory_stats().get("peak_bytes_in_use")
    print(f"peak_bytes_in_use  = {peak}")
    print(f"summary            = {json.dumps(res)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
