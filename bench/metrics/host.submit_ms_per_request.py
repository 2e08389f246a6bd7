"""Host milliseconds per ``submit()`` in the window (the benchmark's
``bench.submit`` spans: request entry and the text tower)."""


def read(ctx):
    spans = ctx["trace"].spans.get("bench.submit", [])
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
