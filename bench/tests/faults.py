"""Faults planted in the timed path, each a function of a
``pytest.MonkeyPatch``: the check has to come out false for every one a
serving cell on one chip can have (there is no exchange between chips
to leave out).

On the chip, at a cell's own size, one fault per process:

    python3 bench/tests/faults.py --fault half_mean \\
        --workload sage-dit-100m.themed-batch --seed <n> --seconds 15

prints the run's result line, as ``bench/run.py`` does.
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def frozen_step(monkeypatch):
    """A segment that returns its state unchanged (the step index still
    advances, so the scheduler goes on as if it had run)."""
    from repro.serving import scheduler as sch

    def frozen(eps_fn, sched, sage, carry, cond, mask, null, n_steps, *a,
               **kw):
        return carry._replace(step_idx=carry.step_idx + n_steps)

    monkeypatch.setattr(sch, "branch_phase", frozen)


def half_mean(monkeypatch):
    """The group-mean conditioning taken over the first half of the
    members only."""
    import jax.numpy as jnp
    from repro.serving import scheduler as sch
    real = sch.group_mean

    def half(x, mask):
        n = x.shape[1]
        keep = (jnp.arange(n) < max(1, n // 2)).astype(mask.dtype)
        return real(x, mask * keep[None])

    monkeypatch.setattr(sch, "group_mean", half)


def altered_answer(monkeypatch):
    """Each answer altered where it is produced: the latent's channels
    come out in reverse order."""
    from repro.serving.scheduler import RequestScheduler
    real = RequestScheduler._decode
    monkeypatch.setattr(RequestScheduler, "_decode",
                        lambda self, z: real(self, z)[..., ::-1])


FAULTS = {f.__name__: f for f in (frozen_step, half_mean, altered_answer)}


def main() -> int:
    import pytest
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fault", choices=sorted(FAULTS), required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    with pytest.MonkeyPatch.context() as mp:
        FAULTS[args.fault](mp)
        result = harness.run(args.workload, args.seed, args.seconds, False)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
