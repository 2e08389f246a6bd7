"""Milliseconds per ``tick()`` in which the device ran nothing (device
idle time inside the benchmark's ``bench.tick`` spans, over the ticks of
the window)."""


def read(ctx):
    idle, n = ctx["trace"].idle_in("bench.tick")
    if n == 0:
        return None
    return 1e3 * idle / n
