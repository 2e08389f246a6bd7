"""Milliseconds per prompt that ``submit`` waits for the text tower's
device work and the copy of its features to the host (the program's
``submit.wait`` spans, the ``np.asarray`` of ``feats`` / ``pooled``), over
the prompts submitted in the window."""
from bench import program_spans


def read(ctx):
    return program_spans.ms_per_prompt(ctx, "submit.wait")
