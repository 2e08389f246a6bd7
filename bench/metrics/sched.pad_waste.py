"""Share of the latent rows launched in the window that were padding
(``summary()`` ``pack_pad_rows`` over ``pack_rows``, differenced over the
window): a group of fewer than 4 members still computes 4 branch rows."""


def read(ctx):
    c = ctx["counts"]
    if c["pack_rows"] <= 0:
        return None
    return c["pack_pad_rows"] / c["pack_rows"]
