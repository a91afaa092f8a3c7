"""95th percentile of the program's own `RequestRecord.queue_wait_s`.
The program stamps admission after the request's prefill, so this wait
includes that prefill."""

import numpy as np


def read(ctx):
    return 1e3 * float(np.percentile(
        [r.queue_wait_s for r in ctx["records"]], 95))
