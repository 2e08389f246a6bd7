"""One run of one benchmark cell: load, build, warm up, measure, check.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

1. Load: the cell (``bench/cells/<cell>.json``), its configuration
   (``bench/configs/<config>.json``) and traffic mix
   (``bench/traffic/<mix>.json``), found by the names in
   ``BENCHMARK.json``.  Refuse without a TPU or with Pallas interpreting.
2. Build: weights on the device from the seed (``weights.py``), the
   program's ``SageServingEngine`` and its ``streaming_scheduler`` with a
   ``TrunkCache`` at its defaults.  Kernel implementations, packing and
   the launch policy stay the program's defaults.
3. Warm up: for every pack width k up to the cell's cap on groups per
   tick, k groups launched together and run to completion (so every
   segment program the window can meet is compiled), then the cell's own
   traffic for ``lead_in_s`` seconds so that groups, packs and the trunk
   cache reach steady state.  All of it is set-up.
4. Measure for ``--seconds``: requests due in the window are timed from
   when they were due to when ``tick()`` returned them; the clients go
   on sending after the window, unmeasured, until every measured request
   is done.
   With ``--trace 1`` the window runs under the JAX profiler and the
   per-layer metrics are read from it.
5. Check: a sample of the window's completions, drawn from the seed,
   against the plain reference (``bench/references/<family>.py``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time
from collections import defaultdict, deque
from contextlib import nullcontext
from typing import Dict, List, Optional

import numpy as np

from bench import flops, generator, guards, weights
from bench.trace_reduce import reduce_file

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
RUNS = ROOT / ".bench_runs"

#: a measured request may take this long past the window's close (after
#: the profiler has stopped, in a traced run)
DRAIN_S = 60.0
#: and a sampled cache hit's source group this much longer again
SOURCE_WAIT_S = 30.0


def clock() -> float:
    return time.perf_counter()


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def load_benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def load_cell(name: str) -> dict:
    return load_json(BENCH / "cells" / f"{name}.json")


def load_config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (metric readers, references)."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the system under test --------------------------------------------------

def model_config(spec: dict, exact: bool = True):
    """The program's registered config with the file's sizes.  ``exact``
    (every benchmark run) requires the registered config to carry them
    already: the yardstick's sizes and the deployed ones must agree."""
    from repro.config import get_config, replace
    cfg = get_config(spec["program_config"])
    want = dict(n_layers=spec["n_layers"], d_model=spec["d_model"],
                n_heads=spec["n_heads"], n_kv_heads=spec["n_heads"],
                d_ff=spec["d_ff"], latent_size=spec["latent_size"],
                latent_channels=spec["latent_channels"],
                patch=spec["patch"], cond_dim=spec["cond_dim"],
                cond_len=spec["cond_len"], qk_norm=spec["qk_norm"],
                rope_theta=spec["rope_theta"], rms_eps=spec["norm_eps"],
                mlp_kind="gelu", dtype=spec["dtype"],
                param_dtype=spec["param_dtype"])
    if exact:
        diff = {k: (getattr(cfg, k), v) for k, v in want.items()
                if getattr(cfg, k) != v}
        if cfg.hd != spec["head_dim"]:
            diff["head_dim"] = (cfg.hd, spec["head_dim"])
        if diff:
            raise ValueError(f"program config {cfg.name!r} differs from "
                             f"bench/configs/{spec['name']}.json: {diff}")
    return replace(cfg, head_dim=spec["head_dim"], **want)


def sage_config(cell: dict):
    from repro.config import SageConfig
    s = cell["sage"]
    return SageConfig(total_steps=s["total_steps"],
                      share_ratio=s["share_ratio"],
                      guidance_scale=s["guidance_scale"],
                      tau_min=s["tau_min"], tau_max=s["tau_max"],
                      clip_x0=s["clip_x0"], sampler=s["sampler"],
                      shared_uncond_cfg=s["shared_uncond_cfg"],
                      adaptive_branch=s["adaptive_branch"])


def sched_seed(seed: int) -> int:
    """The seed handed to the scheduler (its init noise); it keeps 31 bits."""
    return int(seed) % (2 ** 31)


def build(spec: dict, cell: dict, seed: int, *, exact: bool = True,
          tracer=None):
    """(scheduler, dit params, text params): the engine's streaming
    scheduler over weights drawn from the seed."""
    from repro.models import text_encoder as te
    from repro.serving.engine import SageServingEngine
    from repro.serving.trunk_cache import TrunkCache

    class ProvenanceCache(TrunkCache):
        """The trunk cache at its defaults, noting which stored group's
        trunk each lookup returned (the check needs the source)."""

        def __init__(self, **kw):
            super().__init__(**kw)
            self.sources: List[Optional[int]] = []

        def lookup(self, *a, **kw):
            entry = super().lookup(*a, **kw)
            self.sources.append(None if entry is None else entry.rng_fold)
            return entry

    cfg = model_config(spec, exact)
    ts = spec["text_tower"]
    tcfg = te.text_cfg(dim=cfg.cond_dim, layers=ts["layers"])
    if (tcfg.d_model, tcfg.n_heads, tcfg.d_ff, tcfg.vocab) != (
            ts["d_model"], ts["n_heads"], ts["d_ff"], ts["vocab"]):
        raise ValueError("program text tower differs from the config file")
    params, text_params = weights.draw(spec, seed)
    engine = SageServingEngine(cfg, sage_config(cell), dit_params=params,
                               text_params=text_params, text_cfg=tcfg,
                               group_size=cell["group_size"],
                               seed=sched_seed(seed))
    kw = {"tracer": tracer} if tracer is not None else {}
    sched = engine.streaming_scheduler(
        slice_steps=cell["slice_steps"],
        trunk_cache=ProvenanceCache() if cell["trunk_cache"] else None,
        max_groups_per_tick=cell["max_groups_per_tick"], **kw)
    return sched, params, text_params


# -- driving it ---------------------------------------------------------------

class Driver:
    """Submits and ticks the scheduler, one request per ``submit`` as
    independent clients send them, under the benchmark's spans; matches
    completions to requests (first in, first out among equal prompts)
    and notes each launched group's trunk source."""

    def __init__(self, sched, annotate: bool):
        self.sched = sched
        self.annotate = annotate
        self.requests: List[dict] = []
        self.waiting: Dict[str, deque] = defaultdict(deque)
        self.groups: Dict[int, List[int]] = defaultdict(list)
        self.source: Dict[int, int] = {}
        self._known: set = set()
        self._n_lookups = 0

    def span(self, name: str):
        if not self.annotate:
            return nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def submit(self, prompt: str, due: float, **info) -> int:
        with self.span("bench.submit"):
            sent = clock()
            self.sched.submit([prompt], now=sent)
        idx = len(self.requests)
        self.requests.append(dict(prompt=prompt, due=due, sent=sent,
                                  done=None, status=None, gid=None, **info))
        self.waiting[prompt].append(idx)
        return idx

    def tick(self) -> List[int]:
        """One tick; returns the indices of the requests it completed."""
        with self.span("bench.tick"):
            out = self.sched.tick(now=clock())
        t = clock()
        self._note_launches()
        done = []
        for c in out:
            idx = self.waiting[c.prompt].popleft()
            self.requests[idx].update(done=t, status=c.status,
                                      gid=c.group_id, image=c.image,
                                      cache_hit=c.cache_hit)
            self.groups[c.group_id].append(idx)
            done.append(idx)
        return done

    def _note_launches(self) -> None:
        cache = self.sched.trunk_cache
        if cache is None:
            return
        new = [g.gid for g in self.sched.inflight if g.gid not in self._known]
        found = cache.sources[self._n_lookups:]
        self._n_lookups = len(cache.sources)
        if len(new) != len(found):
            raise RuntimeError(f"{len(new)} groups launched but {len(found)} "
                               f"trunk-cache lookups: cannot trace sources")
        for gid, src in zip(new, found):
            self._known.add(gid)
            if src is not None:
                self.source[gid] = src

    def drain(self) -> None:
        while self.sched.pending:
            self.tick()


def warm_up(driver: Driver, cell: dict) -> None:
    """Every pack width: for k = 1 .. cap, k groups (of 1-3 copies of one
    warm-up prompt, so none is full and all launch on the same tick) run
    together from launch to completion."""
    pool = iter(generator.load_prompt_set("warmup")["prompts"])
    for k in range(1, cell["max_groups_per_tick"] + 1):
        for j in range(k):
            prompt = next(pool)
            for _ in range(j % 3 + 1):
                driver.submit(prompt, clock(), phase="warmup")
        driver.drain()


class Window:
    """The measured interval and what the traced run does at its ends."""

    def __init__(self, w0: float, seconds: float, trace_dir=None):
        self.w0, self.w1 = w0, w0 + seconds
        self.trace_dir = trace_dir
        self.opened = self.closed = None
        self._ann = None

    def poll(self, now: float, snapshot) -> None:
        if self.opened is None and now >= self.w0:
            if self.trace_dir is not None:
                _device_sync()
                import jax
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0     # Python calls: too many
                opts.host_tracer_level = 1       # the benchmark's spans
                jax.profiler.start_trace(str(self.trace_dir),
                                         profiler_options=opts)
                self._ann = jax.profiler.TraceAnnotation("bench.window")
                self._ann.__enter__()
            self.opened = clock()
            self.at_open = snapshot()
        if self.closed is None and now >= self.w1:
            if self.trace_dir is not None:
                _device_sync()
                self._ann.__exit__(None, None, None)
                import jax
                jax.profiler.stop_trace()
            self.closed = clock()
            self.at_close = snapshot()


def _device_sync() -> None:
    """Wait until the device has run everything enqueued so far."""
    import jax
    import jax.numpy as jnp
    jax.block_until_ready(jnp.zeros((), jnp.float32) + 1.0)


def drive(driver: Driver, traffic: generator.Traffic, cell: dict,
          seconds: float, window: Window, snapshot,
          needed=lambda d: True) -> None:
    """Run the cell's closed loop from now: lead-in, window, then clients
    go on sending until every measured request is done (at most
    ``DRAIN_S`` past the close) and ``needed(driver)`` holds."""
    prompts = traffic.prompts()
    t0 = clock()
    for c in range(int(cell["clients"])):
        driver.submit(next(prompts), t0, client=c,
                      measured=window.w0 <= t0 < window.w1)
    while True:
        now = clock()
        window.poll(now, snapshot)
        if window.closed is not None:
            open_ = [r for r in driver.requests
                     if r.get("measured") and r["done"] is None]
            if not open_ and needed(driver):
                return
            # from when the window closed: stopping a trace takes time
            if now > window.closed + DRAIN_S + SOURCE_WAIT_S or (
                    open_ and now > window.closed + DRAIN_S):
                return
        for idx in driver.tick():
            r = driver.requests[idx]
            if "client" in r:
                t = r["done"]
                driver.submit(next(prompts), t, client=r["client"],
                              measured=window.w0 <= t < window.w1)


# -- the run ------------------------------------------------------------------

def counters(sched) -> dict:
    out = {k: float(sched.stats[k]) for k in ("nfe", "completed",
                                             "pack_rows", "pack_pad_rows",
                                             "launches")}
    out["ticks"] = float(sched.ticks)
    if sched.trunk_cache is not None:
        out["cache_hits"] = float(sched.trunk_cache.stats["hits"])
        out["cache_misses"] = float(sched.trunk_cache.stats["misses"])
    return out


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile, linear between closest ranks (numpy's)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def pick_sample(driver: Driver, seed: int, k: int) -> List[int]:
    """``k`` measured, completed requests drawn from the seed, half of
    them (where there are any) from groups whose trunk came from the
    cache."""
    ok = [i for i, r in enumerate(driver.requests)
          if r.get("measured") and r["status"] == "ok"]
    hits = [i for i in ok if driver.requests[i]["gid"] in driver.source]
    fresh = [i for i in ok if driver.requests[i]["gid"] not in driver.source]
    rng = np.random.default_rng([int(seed) & (2 ** 64 - 1), 5])
    n_hit = min(len(hits), (k + 1) // 2)
    take = list(rng.permutation(hits)[:n_hit])
    take += list(rng.permutation(fresh)[:k - n_hit])
    if len(take) < k:
        rest = [i for i in ok if i not in take]
        take += list(rng.permutation(rest)[:k - len(take)])
    return sorted(int(i) for i in take)


def run(workload: str, seed: int, seconds: float, trace: bool,
        control: bool = False, *, spec: Optional[dict] = None,
        cell: Optional[dict] = None, require_chip: bool = True,
        process_start: Optional[float] = None, compile_cache: bool = True,
        log=None) -> dict:
    """One run; returns the result record (last key ``checks``).
    ``spec``/``cell`` replace the files (tests run tiny sizes on the CPU,
    with ``require_chip`` and ``compile_cache`` off)."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    start = clock() if process_start is None else process_start
    bench = load_benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    exact = spec is None
    cell = cell or load_cell(workload)
    spec = spec or load_config(entry["config"])
    mix = generator.load_mix(entry["traffic"])
    from repro.kernels.dispatch import resolve_interpret
    if require_chip:
        guards.require_chip(int(entry["chips"]))
    if require_chip and resolve_interpret("auto"):
        raise guards.Refused("Pallas would run in interpret mode")
    cache_dir = guards.enable_compile_cache() if compile_cache else "off"
    compiles = guards.CompileLog()
    try:
        return _run(bench, workload, entry, spec, cell, mix, seed, seconds,
                    trace, control, exact, start, cache_dir, compiles, log)
    finally:
        compiles.close()


def _run(bench, workload, entry, spec, cell, mix, seed, seconds, trace,
         control, exact, start, cache_dir, compiles, log):
    import jax
    dev = jax.devices()[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
        f"compile cache {cache_dir}")

    tracer = None
    if trace:
        from repro.serving.telemetry import Tracer
        tracer = Tracer()
    sched, params, text_params = build(spec, cell, seed, exact=exact,
                                       tracer=tracer)
    driver = Driver(sched, annotate=trace)
    t = clock()
    warm_up(driver, cell)
    log(f"warm-up {clock() - t:.1f} s, {len(compiles.events)} compiles "
        f"so far")
    traffic = generator.Traffic(mix, cell, seed)
    trace_dir = None
    if trace:
        trace_dir = RUNS / f"trace-{workload}-{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    _device_sync()          # compiles the traced run's sync op here
    window = Window(clock() + float(cell["lead_in_s"]), seconds, trace_dir)

    def sources_done(d: Driver) -> bool:
        sample = pick_sample(d, seed, int(cell["check"]["sample"]))
        srcs = {d.source[d.requests[i]["gid"]] for i in sample
                if d.requests[i]["gid"] in d.source}
        return all(len(d.groups.get(s, ())) > 0 for s in srcs)

    drive(driver, traffic, cell, seconds, window, lambda: counters(sched),
          needed=sources_done)
    setup_s = window.w0 - start
    mem = dev.memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))

    measured = [r for r in driver.requests if r.get("measured")]
    done = [r for r in measured if r["status"] == "ok"]
    failed = len(measured) - len(done)
    in_window = [r for r in driver.requests if r["status"] == "ok"
                 and window.w0 <= r["done"] < window.w1]
    lat = [r["done"] - r["due"] for r in done]
    late = [r["sent"] - r["due"] for r in measured]
    log(f"window {seconds:.0f} s: {len(measured)} requests due, "
        f"{len(done)} done, {len(in_window)} completions in the window; "
        f"generator late p50 {percentile(late, 50) * 1e3 if late else 0:.1f}"
        f" ms, max {max(late) * 1e3 if late else 0:.1f} ms")

    in_w = compiles.between(window.w0, window.w1)
    w_compiles = [e for e in in_w if not e[3]]
    w_loads = [e for e in in_w if e[3]]
    log(f"inside the window: {len(w_compiles)} compiles "
        f"{sorted({e[1] for e in w_compiles})}, {len(w_loads)} programs "
        f"loaded from the compile cache {sorted({e[1] for e in w_loads})}")
    result: dict = {"correct": False, "attempted": len(measured),
                    "failed": failed}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    if trace:
        tr = _reduce_trace(trace_dir)
        layer = _per_layer(bench, workload, spec, dev, window, compiles,
                           tr, tracer, driver)
        result["metrics"] = layer
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        result["breakdown"] = tr.breakdown()
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        result["metrics"] = {
            "images_per_s": {"value": len(in_window) / seconds,
                             "unit": "images/s"},
            "latency_p50_s": {"value": percentile(lat, 50) if lat
                              else math.nan, "unit": "s"},
            "latency_p90_s": {"value": percentile(lat, 90) if lat
                              else math.nan, "unit": "s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    result["device"] = device
    log(f"setup {setup_s:.2f} s, latency samples {len(lat)}, peak bytes "
        f"{peak_bytes}")

    # -- check, with the program's state freed
    sample = pick_sample(driver, seed, int(cell["check"]["sample"]))
    groups = {gid: [driver.requests[i]["prompt"] for i in idx]
              for gid, idx in driver.groups.items()}
    served = []
    for i in sample:
        r = driver.requests[i]
        served.append((r["gid"], driver.groups[r["gid"]].index(i),
                       r["image"]))
    source = {gid: driver.source[gid] for gid, _, _ in served
              if gid in driver.source}
    del sched, driver, tracer
    gc.collect()
    from bench.check import check
    t = clock()
    checks = check(spec, cell, params, text_params, sched_seed(seed),
                   groups, source, served, failed, control=control, log=log)
    log(f"check {clock() - t:.1f} s over {len(served)} sampled requests")
    # with nothing compared there is no evidence either way
    result["correct"] = bool(served) and all(
        v <= lim for v, lim in checks.values())
    result["checks"] = checks
    for k, (v, lim) in checks.items():
        log(f"check {k} {v:.6g} limit {lim}")
    return result


def _reduce_trace(trace_dir: pathlib.Path):
    files = sorted(trace_dir.glob("**/*.xplane.pb"))
    if not files:
        raise RuntimeError(f"no profiler trace under {trace_dir}")
    return reduce_file(files[-1])


def _per_layer(bench: dict, workload: str, spec: dict, dev, window: Window,
               compiles, tr, tracer, driver) -> dict:
    ctx = {
        "spec": spec, "device_kind": dev.device_kind,
        "row_eval_flops": flops.row_eval_flops(spec),
        "trace": tr, "tracer": tracer,
        "window": (window.opened, window.closed),
        "counts": {k: window.at_close[k] - window.at_open.get(k, 0.0)
                   for k in window.at_close},
        "compiles": compiles.between(window.opened, window.closed),
    }
    out = {}
    for m in bench["per_layer"]:
        if workload not in m.get("workloads", [workload]):
            continue
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
