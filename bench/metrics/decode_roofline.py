"""The served decode program's share of its roofline, in percent: the
least time the chip could take for the window's decode work, the larger
of its FLOPs over peak FLOP/s and its minimal bytes over peak HBM
bandwidth (the family's `counts.window_work` of the traced window), over
the device time of the compiled decode program (`_fused_continuous_fn`)
in the trace."""

MODULE = "_fused_continuous_fn"


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    device_s = sum(v for k, v in tr["module_s"].items() if MODULE in k)
    if device_s <= 0:
        return None
    work, peaks = tr["work"], ctx["peaks"]
    least = max(work["decode_flops"] / peaks["bf16_flops_per_s"],
                work["decode_bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / device_s
