"""Median seconds from a request's arrival (``submit``) to its group's
launch, over the requests launched in the window (the program's
``request.queue`` spans, one per member, emitted at ``group.launch``)."""
import statistics

from bench import program_spans


def read(ctx):
    w0, w1 = ctx["trace"].window
    waits = [b - a for a, b, _ in program_spans.spans(ctx, "request.queue")
             if w0 <= b < w1]
    if not waits:
        return None
    return statistics.median(waits)
