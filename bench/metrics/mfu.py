"""Model FLOPs of the window (every prefill and decode token, the
family's `counts`) over the window's wall seconds times the chip's bf16
peak, in percent."""


def read(ctx):
    work = ctx["work"]
    flops = work["prefill_flops"] + work["decode_flops"]
    return 100.0 * flops / (ctx["window_s"]
                            * ctx["peaks"]["bf16_flops_per_s"])
