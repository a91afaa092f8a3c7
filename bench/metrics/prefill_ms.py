"""Mean milliseconds per prefill call, seed batches and single-row
admissions together (`prefill_s / prefill_calls`, host clock around calls
that end in a device sync)."""


def read(ctx):
    st = ctx["stats"]
    return 1e3 * st.prefill_s / st.prefill_calls if st.prefill_calls else None
