"""Share of the traced window in which no operation ran on the device, in
percent (`trace_reduce`: 1 - busy union / window)."""


def read(ctx):
    tr = ctx["trace"]
    return None if tr is None else 100.0 * tr["idle_share"]
