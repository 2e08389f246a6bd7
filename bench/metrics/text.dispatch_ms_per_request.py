"""Host milliseconds per prompt spent lowering and dispatching the text
tower in ``submit`` (the program's ``submit.dispatch`` spans: tokenize and
``encode_text`` up to its return), over the prompts submitted in the
window.  With ``text.wait_ms_per_request`` it splits
``host.submit_ms_per_request``."""
from bench import program_spans


def read(ctx):
    return program_spans.ms_per_prompt(ctx, "submit.dispatch")
