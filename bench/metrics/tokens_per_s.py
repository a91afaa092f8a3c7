"""Output tokens of the window over the wall seconds of the window call."""


def read(ctx):
    return sum(r.n_tokens for r in ctx["records"]) / ctx["window_s"]
