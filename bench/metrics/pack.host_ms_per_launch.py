"""Host milliseconds spent packing carries for a segment launch and
scattering its result back per group (the program's ``pack.build`` and
``pack.scatter`` spans, eager concatenates, pads and slices), per
``phase.shared`` / ``phase.branch`` launch in the window."""
from bench import program_spans


def read(ctx):
    launches = sum(len(program_spans.in_window(ctx, f"phase.{p}"))
                   for p in ("shared", "branch"))
    if launches == 0:
        return None
    host = sum(program_spans.seconds(program_spans.in_window(ctx, name))
               for name in ("pack.build", "pack.scatter"))
    return 1e3 * host / launches
