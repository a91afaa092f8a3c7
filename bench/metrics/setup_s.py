"""Seconds from process start to the start of the window: imports,
weights, engine build, warm-up and any compilation."""


def read(ctx):
    return ctx["setup_s"]
