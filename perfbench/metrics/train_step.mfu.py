"""The whole step's share of the chip's peak: forward and backward FLOPs of one
gradient step from shapes (the adapter's `step_flops`) x gradient steps in the
traced window, over window x peak FLOP/s. All the time of the window counts,
idle included."""


def read(ctx):
    win = ctx["window"]
    if ctx["rehearse"] or win["grad_steps"] <= 0 or win["seconds"] <= 0:
        return None
    flops = ctx["adapter"].step_flops(ctx["shapes"], ctx["spec"])["total"]
    peak = ctx["peaks"].lookup(ctx["device_kind"])["flops_per_s"]
    return 100.0 * flops * win["grad_steps"] / (win["seconds"] * peak)
