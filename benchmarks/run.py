"""Benchmark entry point — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig3,...]

Prints ``name,us_per_call,derived`` CSV.  Quality benches train/cache the
three Table-1 models on first run (experiments/bench_cache/).

Regression gate (the CI ``bench-regression`` job):

    PYTHONPATH=src python -m benchmarks.run --check benchmarks/BENCH_4.json \
        --tol 50

re-runs the suites the baseline snapshot covers and fails (exit 1) if any
row regressed: ``nfe=`` in ``derived`` must match EXACTLY (NFE is the
backend-independent work ledger — any drift is a correctness bug, not
noise), and ``us`` must stay within ``--tol`` percent of the baseline
(wall time prices the interpret-mode call graph off-TPU; the tolerance
absorbs runner jitter, the exact-NFE bar does the real gating).  Rows
missing from the current run fail too.  ``--json PATH`` additionally
writes the rows as a BENCH_N-style snapshot fragment (the nightly
workflow uploads it as an artifact)."""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List, Optional, Tuple

Row = Tuple[str, float, str]


def _derived_map(derived: str) -> Dict[str, str]:
    """Parse 'k1=v1 k2=v2 ...' derived strings; bare tokens are skipped."""
    out = {}
    for tok in derived.split():
        if "=" in tok:
            k, v = tok.split("=", 1)
            out[k] = v
    return out


def check_rows(baseline: dict, rows: List[Row], tol_pct: float
               ) -> List[str]:
    """Compare a current run against a committed BENCH_N snapshot.

    Returns a list of human-readable regression messages (empty = pass):
    missing rows, any ``nfe=`` mismatch (exact), and ``us`` above
    ``baseline * (1 + tol_pct/100)``.  Faster-than-baseline is never a
    failure."""
    current = {name: (us, derived) for name, us, derived in rows}
    problems = []
    for brow in baseline["rows"]:
        name = brow["name"]
        if name not in current:
            problems.append(f"{name}: row missing from current run")
            continue
        us, derived = current[name]
        b_derived = _derived_map(brow["derived"])
        c_derived = _derived_map(derived)
        if "nfe" in b_derived:
            if float(c_derived.get("nfe", "nan")) != float(b_derived["nfe"]):
                problems.append(
                    f"{name}: NFE {c_derived.get('nfe')} != baseline "
                    f"{b_derived['nfe']} (exact match required)")
        limit = brow["us"] * (1.0 + tol_pct / 100.0)
        if us > limit:
            problems.append(
                f"{name}: {us:.1f} us > {limit:.1f} us "
                f"(baseline {brow['us']:.1f} + {tol_pct:g}% tol)")
    return problems


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated suite names to run")
    ap.add_argument("--check", default="",
                    help="BENCH_N.json baseline to gate against (runs the "
                         "suites its rows cover; exit 1 on regression)")
    ap.add_argument("--tol", type=float, default=50.0,
                    help="us tolerance (percent) for --check; NFE is "
                         "always exact")
    ap.add_argument("--json", default="",
                    help="write the rows as a JSON snapshot fragment")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import (beyond_paper, cost_model, fig3_similarity,
                            fig4_shared_steps, kernel_bench, roofline_report,
                            sampler_e2e, serving_bench, table1_quality)
    suites = {
        "cost_model": cost_model.main,
        "kernels": kernel_bench.main,
        "sampler": sampler_e2e.main,
        "serving": serving_bench.main,
        "roofline": roofline_report.main,
        "table1": table1_quality.main,
        "fig3": fig3_similarity.main,
        "fig4": fig4_shared_steps.main,
        "beyond": beyond_paper.main,
    }

    baseline = None
    if args.check:
        with open(args.check) as f:
            baseline = json.load(f)
        needed = {r["name"].split("/")[0] for r in baseline["rows"]}
        unknown = needed - set(suites)
        if unknown:
            print(f"--check baseline names unknown suites: {unknown}",
                  file=sys.stderr)
            return 2
        only = needed if only is None else (only & needed)
        if not only:
            print(f"--only {args.only!r} selects none of the baseline's "
                  f"suites ({sorted(needed)}) — nothing to gate",
                  file=sys.stderr)
            return 2
        print(f"# regression gate vs {args.check} "
              f"(suites: {','.join(sorted(only))}, tol {args.tol:g}%)",
              file=sys.stderr)

    print("name,us_per_call,derived")
    rows: List[Row] = []
    for name, fn in suites.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            fn(rows)
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,0,{e!r}", file=sys.stderr)
            raise
        print(f"# suite {name} done in {time.time()-t0:.1f}s",
              file=sys.stderr)

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": [{"name": n, "us": u, "derived": d}
                                for n, u, d in rows]}, f, indent=1)
        print(f"# rows written to {args.json}", file=sys.stderr)

    if baseline is not None:
        problems = check_rows(baseline, rows, args.tol)
        for p in problems:
            print(f"::error::bench regression: {p}", file=sys.stderr)
        if problems:
            return 1
        print(f"# bench gate PASS: {len(baseline['rows'])} rows within "
              f"{args.tol:g}% (NFE exact)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
