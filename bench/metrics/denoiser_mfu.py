"""The denoiser's share of the chip's bf16 peak, in %.

Useful FLOPs: the row-evals of the segment launches dispatched in the
window (the scheduler's ``phase.shared`` / ``phase.branch`` launch spans:
2 CFG rows x (rows - pad rows) x steps), times the FLOPs of one row-eval
from the configuration's shapes (``bench/flops.py``).  Time: the device
time of the ``shared_segment`` / ``branch_segment`` programs in the
trace.  Pad rows cost time and add no useful FLOPs.  The peak is the
bf16 peak of the run's ``device_kind`` (``bench/peaks.py``)."""
from bench.peaks import peaks


def read(ctx):
    tr, tracer = ctx["trace"], ctx["tracer"]
    w0, w1 = ctx["window"]
    rows = 0.0
    for e in tracer.events:
        if e.name in ("phase.shared", "phase.branch") and w0 <= e.ts < w1:
            a = e.args
            rows += 2.0 * (a["rows"] - a["pad_rows"]) * a["n_steps"]
    secs = sum(s for name, s in tr.program_s.items()
               if name.endswith(("shared_segment", "branch_segment")))
    if rows <= 0 or secs <= 0:
        return None
    peak = peaks(ctx["device_kind"])["bf16_flops"]
    return 100.0 * rows * ctx["row_eval_flops"] / secs / peak
