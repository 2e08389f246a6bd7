"""Share of the window's trunk-cache lookups that found a trunk
(``TrunkCache.stats`` hits over hits + misses, differenced over the
window)."""


def read(ctx):
    c = ctx["counts"]
    n = c.get("cache_hits", 0.0) + c.get("cache_misses", 0.0)
    if n <= 0:
        return None
    return c["cache_hits"] / n
