"""Share of the pool's slot-steps in the untraced window that stood
vacant while an arrived request waited that the shared KV clock could not
admit (`ContinuousStats.empty_slot_steps_blocked` over `n_slots` times
`decode_steps`), in percent."""


def read(ctx):
    st = ctx["stats"]
    blocked = getattr(st, "empty_slot_steps_blocked", None)
    if blocked is None or not st.decode_steps:
        return None
    return 100.0 * blocked / (ctx["n_slots"] * st.decode_steps)
