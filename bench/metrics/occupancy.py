"""Mean live slots per decode step over the pool's width, in percent
(`ContinuousStats.mean_occupancy / n_slots`)."""


def read(ctx):
    return 100.0 * ctx["stats"].mean_occupancy / ctx["n_slots"]
