"""The grouped expert products' share of their roofline: the FLOPs of the
(token, expert) pairs the program's `moe_load` events counted (forward and
backward, three kernels a pair) and the bytes of the held experts' kernels
(read forward, read again for the input's gradient, their gradient written),
whichever bound is the longer at the chip's peaks, over the device time of the
ops under the `jax.named_scope` "experts", per gradient step. The counts'
functions live with the cell's adapter; padding rows and recompute are not
work the pairs need, so they lower the share."""
from perfbench import program_events, span_reduce


def read(ctx):
    ms = span_reduce.part_ms(ctx, "experts")
    if ms is None or ms <= 0 or ctx.get("rehearse"):  # another step's capture, a program without the scope, a CPU run
        return None
    adapter, loads = ctx["adapter"], program_events.events(ctx, "moe_load")
    if not loads or not hasattr(adapter, "expert_flops"):
        return None
    grad_steps = len(loads) * span_reduce.steps_per_call(ctx)
    pairs = sum(e["routed_here"] for e in loads) / grad_steps
    peaks = ctx["peaks"].lookup(ctx["device_kind"])
    bound_s = max(adapter.expert_flops(ctx["shapes"], ctx["spec"], pairs) / peaks["flops_per_s"],
                  3.0 * adapter.expert_bytes(ctx["shapes"]) / peaks["hbm_bytes_per_s"])
    return 100.0 * bound_s / (ms * 1e-3)
