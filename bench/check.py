"""The comparison that decides ``correct``.

A sample of the window's completions (drawn from the seed by the
harness) is recomputed by the plain reference of the configuration's
family, at the timed sizes, from the same weights (drawn by the
benchmark, not the program), the same prompts and the group structure
the program reported: each sampled request's group, its member prompts,
and, where the trunk came from the cache, the group that stored it.

Numbers compared, each with its limit (the cell's ``check.limits``):

* ``latent_err_ratio``: how far the served final latents lie from the
  float32 reference, in units of how far the reference itself moves when
  computed in the configuration's own precision (bfloat16):
  RMS_i ||served_i - ref_i|| / ||ref_i|| over RMS_i ||bf16_i - ref_i||
  / ||ref_i||.  Thirty guided steps amplify rounding by an amount that
  depends on the seed's weights and prompts; the unit cancels it, so a
  sound run reads about 1 on every seed.
* ``grouping``: sampled groups (and trunk sources) that break what the
  configuration states: a pair of members at or below ``tau_min``, or a
  cache hit whose centroid lies below ``tau_trunk`` of its source's (both
  by the reference's text tower, 0.01 of room for its rounding).
  Limit 0.
* ``unanswered``: measured requests that never completed or were not
  served, plus sampled cache hits whose source could not be traced.
  Limit 0.

With ``control=True`` the control takes the program's place: the
sampled completions are the reference's own, computed in float8, and the
same comparison runs on them; ``latent_err_ratio`` has to read above its
limit, so the run comes out not correct.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Tuple

import numpy as np

ROOM = 0.01


def reference_module(family: str):
    """``bench/references/<family>.py``."""
    return importlib.import_module(f"bench.references.{family}")


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def check(spec: dict, cell: dict, params, text_params, sched_seed: int,
          groups: Dict[int, List[str]], source: Dict[int, int],
          served: List[Tuple[int, int, np.ndarray]], failed: int,
          control: bool = False, log=print) -> Dict[str, Tuple[float, float]]:
    """``served``: (group id, member index, served latent) per sampled
    request; ``groups``: group id -> member prompts in member order;
    ``source``: group id -> the group whose cached trunk it forked from."""
    ref = reference_module(spec["reference"])
    limits = cell["check"]["limits"]
    sage = cell["sage"]
    unanswered = failed
    jobs: Dict[int, dict] = {}
    for gid, m, _ in served:
        src = source.get(gid, gid)
        if src not in groups:
            unanswered += 1
            continue
        jobs.setdefault(gid, {"gid": gid, "source": src, "members": []})
        jobs[gid]["members"].append(m)
    job_list = list(jobs.values())

    violations = 0
    for job in job_list:
        _, pooled = ref.embed(spec, text_params, groups[job["gid"]])
        sim = pooled @ pooled.T
        n = len(pooled)
        if n > 1 and sim[np.triu_indices(n, 1)].min() <= sage["tau_min"] - ROOM:
            violations += 1
        if job["source"] != job["gid"]:
            _, sp = ref.embed(spec, text_params, groups[job["source"]])
            c, cs = pooled.mean(0), sp.mean(0)
            cos = float(c @ cs / np.linalg.norm(c) / np.linalg.norm(cs))
            if cos < cell["check"]["tau_trunk"] - ROOM:
                violations += 1

    want = [(gid, m) for gid, m, _ in served
            if gid in jobs and m in jobs[gid]["members"]]
    served_at = {(gid, m): img for gid, m, img in served}

    def latents(mode):
        traj = ref.Trajectories(spec, sage, params, text_params, mode)
        return ref.group_latents(traj, spec, sched_seed, groups, job_list)

    f32, bf16 = latents("f32"), latents("bf16")
    if control:
        low = latents("fp8")
        served_at = {k: low[k] for k in want}
    own = np.array([_rel(bf16[k], f32[k]) for k in want])
    dev = np.array([_rel(served_at[k], f32[k]) for k in want])

    def ratio(d):
        return float(np.sqrt(np.mean(d ** 2)) / np.sqrt(np.mean(own ** 2))
                     ) if len(want) else 0.0

    who = "float8 control" if control else "served"
    log(f"latent rel L2 against float32, {who} | bf16 reference: "
        + ", ".join(f"g{g}m{m}{'*' if g in source else ''} {a:.4g}|{b:.4g}"
                    for (g, m), a, b in zip(want, dev, own)))
    return {"latent_err_ratio": (ratio(dev), limits["latent_err_ratio"]),
            "grouping": (float(violations), 0.0),
            "unanswered": (float(unanswered), 0.0)}
