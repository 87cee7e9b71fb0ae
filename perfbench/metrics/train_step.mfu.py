"""The whole step's share of the chip's peak: forward and backward FLOPs of one
gradient step from shapes (work.py) x gradient steps in the traced window, over
window x peak FLOP/s. All the time of the window counts, idle included."""


def read(ctx):
    win = ctx["window"]
    if ctx["rehearse"] or win["grad_steps"] <= 0 or win["seconds"] <= 0:
        return None
    w = ctx["spec"]["config"]["widths"]
    flops = ctx["work"].train_step_flops(
        ctx["shapes"], int(w["per_rank_sequence_length"]), int(w["per_rank_batch_size"]), int(w["horizon"]))["total"]
    peak = ctx["peaks"].lookup(ctx["device_kind"])["flops_per_s"]
    return 100.0 * flops * win["grad_steps"] / (win["seconds"] * peak)
