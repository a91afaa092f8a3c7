"""Device milliseconds per decode step: the device time of the served
decode program (`_fused_continuous_fn`) in the traced window over that
window's decode steps, the sum of `steps` on its `engine.chunk.fetch`
spans (`spans.py`)."""

import spans

MODULE = "_fused_continuous_fn"


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    found = spans.analysis()
    if found is None or not found["steps"]:
        return None
    device_s = sum(v for k, v in tr["module_s"].items() if MODULE in k)
    return 1e3 * device_s / found["steps"] if device_s > 0 else None
