"""95th percentile over all requests of the window of admit - arrival on
the engine's clock (see ttft_p50_ms)."""

import numpy as np


def read(ctx):
    return 1e3 * float(np.percentile(
        [r.admit_s - r.arrival_s for r in ctx["records"]], 95))
