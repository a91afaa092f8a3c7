"""Median over all requests of the window of admit - arrival on the
engine's clock: the wait plus the request's own prefill, which yields its
first token."""

import numpy as np


def read(ctx):
    return 1e3 * float(np.percentile(
        [r.admit_s - r.arrival_s for r in ctx["records"]], 50))
