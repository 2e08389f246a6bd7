"""Row-evals (denoiser evaluations of one latent row, the scheduler's NFE)
per image completed in the window: ``summary()['nfe']`` over
``summary()['completed']``, differenced over the window."""


def read(ctx):
    c = ctx["counts"]
    if c["completed"] <= 0:
        return None
    return c["nfe"] / c["completed"]
