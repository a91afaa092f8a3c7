"""95th percentile over requests of (finish - admit) / (tokens - 1): the
mean gap between a request's output tokens, on the engine's clock."""

import numpy as np


def read(ctx):
    per_req = [(r.finish_s - r.admit_s) / (r.n_tokens - 1)
               for r in ctx["records"] if r.n_tokens > 1]
    return 1e3 * float(np.percentile(per_req, 95)) if per_req else None
