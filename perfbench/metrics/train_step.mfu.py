"""The whole step's share of the chip's peak: the adapter's `step_flops`, from
shapes: forward and backward FLOPs of one gradient step ('total') x gradient
steps in the traced window, plus the player's forwards for one env step
('per_env_step'; a prefill spread over the steps it serves) x the window's env
steps, over window x peak FLOP/s. Every adapter counts both, so the name means
one thing in every cell. All the time of the window counts, idle included."""


def read(ctx):
    win = ctx["window"]
    if ctx["rehearse"] or win["grad_steps"] <= 0 or win["seconds"] <= 0:
        return None
    flops = ctx["adapter"].step_flops(ctx["shapes"], ctx["spec"])
    done = flops["total"] * win["grad_steps"] + flops["per_env_step"] * win["env_steps"]
    peak = ctx["peaks"].lookup(ctx["device_kind"])["flops_per_s"]
    return 100.0 * done / (win["seconds"] * peak)
