"""Milliseconds per tick in which the device ran nothing while the host
selected, packed, dispatched and unpacked segments (device idle inside the
program's ``tick.advance`` spans, over the ticks of the window)."""
from bench import program_spans


def read(ctx):
    return program_spans.idle_ms_per_tick(ctx, ("advance",))
