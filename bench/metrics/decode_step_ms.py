"""Mean milliseconds per decode step of the slot pool (`decode_s /
decode_steps`, host clock around chunks that end in a device sync)."""


def read(ctx):
    st = ctx["stats"]
    return 1e3 * st.decode_s / st.decode_steps if st.decode_steps else None
