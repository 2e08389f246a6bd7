"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
its per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown``, and last ``checks`` (each compared number beside its
limit, also the last lines on standard error).  Without a TPU, with
Pallas interpreting, or on any failure, the run exits non-zero and
prints no result line.

``--control 1`` puts the float8 control in the program's place: the
check compares the reference's own completions, computed in float8,
instead of the served ones, and has to come out not correct (it reads
the check's upper end; benchmark runs leave it off).
"""
import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from bench import guards, harness
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), bool(args.control),
                             process_start=PROCESS_START)
    except guards.Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
