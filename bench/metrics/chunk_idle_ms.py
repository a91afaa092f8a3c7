"""Device idle milliseconds per decode chunk in the traced window: idle
time under an `engine.*` span of the serving loop (`spans.py`: each idle
interval goes to the innermost span over its midpoint) over the window's
chunks (its `engine.chunk.fetch` spans)."""

import spans


def read(ctx):
    if ctx["trace"] is None:
        return None
    found = spans.analysis()
    if found is None or not found["chunks"]:
        return None
    return 1e3 * found["named_idle_s"] / found["chunks"]
