"""Milliseconds per tick in which the device ran nothing while the host
fetched finished latents and built completions (device idle inside the
program's ``tick.complete`` spans, over the ticks of the window)."""
from bench import program_spans


def read(ctx):
    return program_spans.idle_ms_per_tick(ctx, ("complete",))
