"""Host milliseconds per decode chunk of the untraced window: the seconds
of the serving loop's `engine.chunk.upload`, `engine.chunk.fetch` and
`engine.bookkeep` spans (`ContinuousStats.phase_s`) over its chunks
(`ContinuousStats.chunks`).  The wait on the device and the prefills are
left out."""

PHASES = ("engine.chunk.upload", "engine.chunk.fetch", "engine.bookkeep")


def read(ctx):
    st = ctx["stats"]
    chunks = getattr(st, "chunks", 0)
    if not chunks:
        return None
    return 1e3 * sum(st.phase_s.get(p, 0.0) for p in PHASES) / chunks
