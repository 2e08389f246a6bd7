import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import (device count locks on first init).

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
mesh, print memory/cost analysis, extract roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod

One JSON per case lands in experiments/dryrun/ (safe for parallel runs).
"""
import argparse
import json
import pathlib
import re
import time
import traceback

import jax

from repro.config import SHAPES, get_config, list_archs
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_case

# per-chip peaks live in the import-safe repro.launch.costs (importing
# *this* module mutates XLA_FLAGS; reports must not pay that).  The
# production mesh is made of v5e chips.
from repro.launch.costs import chip_peaks  # noqa: E402

V5E = chip_peaks("TPU v5 lite")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s*(?:\()?\s*((?:\w+\[[\d,]*\][^)=]*?)+?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def collective_bytes(hlo_text: str):
    """Sum per-device output bytes of every cross-device collective, by kind."""
    out = {}
    for m in _COLL_RE.finditer(hlo_text):
        shapes, kind = m.group(1), m.group(2)
        total = 0
        for sm in _SHAPE_RE.finditer(shapes):
            dt, dims = sm.group(1), sm.group(2)
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0) + total
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def _measure(arch, shape_name, mesh, smoke, kw):
    """lower+compile one build; returns (flops, bytes, coll, compiled, dt)."""
    case = build_case(arch, shape_name, mesh, smoke=smoke, **kw)
    donate = case.static.get("donate", ())
    t0 = time.time()
    with mesh:
        lowered = jax.jit(case.fn, donate_argnums=donate).lower(*case.args)
        compiled = lowered.compile()
    dt = time.time() - t0
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    cost = dict(cost or {})
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(compiled.as_text())
    return flops, bytes_acc, coll, compiled, case, dt


def _n_blocks_full(cfg) -> int:
    per = len(cfg.pattern) if cfg.pattern else 1
    prefix = cfg.moe.first_moe_layer if cfg.family == "moe" else 0
    return (cfg.n_layers - prefix - len(cfg.remainder)) // per


# §Perf hillclimb variants: name -> builder kwargs
VARIANTS = {
    "chunked": {"attn_impl": "chunked"},          # online-softmax attention
    "chunked4k": {"attn_impl": "chunked", "attn_block": 4096},
    "chunked8k": {"attn_impl": "chunked", "attn_block": 8192},
    "chunked512": {"attn_impl": "chunked", "attn_block": 512},
    "dp_only": {"no_tp": True},                   # replicate params (sage)
    "seqshard": {"cache_seq_shard": True},        # KV cache seq over model
    "chunked_seqshard": {"attn_impl": "chunked", "cache_seq_shard": True},
    "adafactor": {"optim": "adafactor"},          # factored opt state
    "noremat": {"remat": False},
    "chunked_noremat": {"attn_impl": "chunked", "remat": False},
}


def run_case(arch: str, shape_name: str, multi_pod: bool, smoke: bool = False,
             outdir: str = "experiments/dryrun", variant: str = "",
             builder_kw=None, fast: bool = False):
    """Roofline measurement per case:

    1. FULL config, scan-lowered -> proves .lower().compile() succeeds on
       the production mesh and yields memory_analysis (real buffer sizes).
    2. Two small UNROLLED variants (k1/k2 scanned blocks) -> per-block
       flops/bytes/collectives by exact linear extrapolation; HLO cost
       analysis counts while-loop bodies once, so scanned full configs
       undercount ~n_layers x, while full unrolls compile too slowly.
    """
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    kw = dict(VARIANTS.get(variant, {}))
    kw.update(builder_kw or {})
    cfg = get_config(arch, smoke=smoke)
    nb_full = _n_blocks_full(cfg)

    # --- 1. full config, scan lowering ---------------------------------
    f_full, b_full, c_full, compiled, case, t_full = _measure(
        arch, shape_name, mesh, smoke, {**kw, "unroll": False})
    mem = compiled.memory_analysis()
    t_compile = t_full

    # --- 2. extrapolation pair ------------------------------------------
    k1, k2 = (2, 5) if nb_full >= 5 else (1, max(2, nb_full))
    if fast:      # multi-pod pass: compile proof only (roofline is 16x16)
        flops, bytes_acc, coll = f_full, b_full, c_full
    elif k2 > k1:
        f1, b1, c1, _, _, t1 = _measure(arch, shape_name, mesh, smoke,
                                        {**kw, "unroll": True,
                                         "n_blocks": k1})
        f2, b2, c2, _, _, t2 = _measure(arch, shape_name, mesh, smoke,
                                        {**kw, "unroll": True,
                                         "n_blocks": k2})
        t_compile += t1 + t2

        def extrap(v1, v2):
            body = (v2 - v1) / (k2 - k1)
            return max(v1 - k1 * body, 0.0) + nb_full * body

        flops = extrap(f1, f2)
        bytes_acc = extrap(b1, b2)
        coll = {k: extrap(c1.get(k, 0), c2.get(k, 0))
                for k in set(c1) | set(c2)}
    else:
        flops, bytes_acc, coll = f_full, b_full, c_full

    if shape_name == "sage_serve":
        K, N = case.static["batch"], case.static["seq"]
        n_lat = (cfg.latent_size // cfg.patch) ** 2
        token_passes = 2 * (K + K * N) * n_lat          # CFG doubles evals
        model_flops = 2.0 * cfg.n_params() * token_passes
    else:
        tokens = SHAPES[shape_name].global_batch * (
            SHAPES[shape_name].seq_len
            if SHAPES[shape_name].kind != "decode" else 1)
        model_flops = 6.0 * cfg.n_active_params() * tokens
        if SHAPES[shape_name].kind == "train":
            model_flops *= 3.0  # fwd + bwd

    # cost_analysis runs on the post-SPMD (per-device) module
    res = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": int(n_chips), "variant": variant or "baseline",
        "compile_s": round(t_compile, 2),
        "full_scan_compile_s": round(t_full, 2),
        "flops_per_dev": flops, "bytes_per_dev": bytes_acc,
        "collective_bytes_per_dev": coll,
        "compute_term_s": flops / V5E.flops,
        "memory_term_s": bytes_acc / V5E.hbm_bw,
        "collective_term_s": coll["total"] / V5E.ici_bw,
        "model_flops_global": model_flops,
        "useful_flops_ratio": (model_flops / (flops * n_chips)
                               if flops else 0.0),
        "memory_analysis": {
            k: getattr(mem, k) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")
            if mem is not None and hasattr(mem, k)},
        "static": case.static,
    }
    terms = {"compute": res["compute_term_s"], "memory": res["memory_term_s"],
             "collective": res["collective_term_s"]}
    res["bottleneck"] = max(terms, key=terms.get)

    pathlib.Path(outdir).mkdir(parents=True, exist_ok=True)
    tag = f"{arch}_{shape_name}_{res['mesh']}"
    if variant:
        tag += f"_{variant}"
    with open(f"{outdir}/{tag}.json", "w") as f:
        json.dump(res, f, indent=1)
    print(f"[dryrun] {tag}: compile={t_compile:.1f}s "
          f"flops/dev={flops:.3e} bytes/dev={bytes_acc:.3e} "
          f"coll/dev={coll['total']:.3e} bottleneck={res['bottleneck']}")
    print(f"  memory_analysis: {res['memory_analysis']}")
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + ["sage_serve", None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--variant", default="")
    ap.add_argument("--fast", action="store_true",
                    help="full-config compile proof only (no roofline "
                         "extrapolation) — used for the multi-pod pass")
    args = ap.parse_args()

    from repro.configs import ASSIGNED
    archs = ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]

    failures = []
    for arch in archs:
        for shape in shapes:
            tag = f"{arch}_{shape}"
            out = pathlib.Path(args.out) / (
                f"{arch}_{shape}_{'2x16x16' if args.multi_pod else '16x16'}"
                + (f"_{args.variant}" if args.variant else "") + ".json")
            if args.all and out.exists():
                print(f"[dryrun] skip existing {out}")
                continue
            try:
                run_case(arch, shape, args.multi_pod, smoke=args.smoke,
                         outdir=args.out, variant=args.variant,
                         fast=args.fast)
            except Exception as e:  # noqa: BLE001
                failures.append((tag, repr(e)))
                print(f"[dryrun] FAIL {tag}: {e}")
                traceback.print_exc()
    if failures:
        print(f"[dryrun] {len(failures)} failures:")
        for t, e in failures:
            print(" ", t, e[:200])
        raise SystemExit(1)
    print("[dryrun] all cases OK")


if __name__ == "__main__":
    main()
