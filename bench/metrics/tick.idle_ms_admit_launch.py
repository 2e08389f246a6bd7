"""Milliseconds per tick in which the device ran nothing while the host
admitted and grouped arrivals and launched groups (device idle inside the
program's ``tick.admit`` and ``tick.launch`` spans, over the ticks of the
window).  With ``tick.idle_ms_advance`` and ``tick.idle_ms_complete`` it
splits ``host.idle_ms_per_tick``."""
from bench import program_spans


def read(ctx):
    return program_spans.idle_ms_per_tick(ctx, ("admit", "launch"))
