"""The two-part attention's share of its roofline: the FLOPs it must do (its
projections for every row and the unmasked (query, key) pairs, forward and
backward) at the chip's peak FLOP/s, or the bytes it must move (its kernels
three times, the rows in and out) at the chip's HBM bandwidth, whichever bound
is the longer, over the device time of the ops under the `jax.named_scope`
"bd_attn" per gradient step. The counts' functions live with the cell's
adapter; remat's recompute is not work the step needs, so it lowers the share."""
from perfbench import span_reduce


def read(ctx):
    ms = span_reduce.part_ms(ctx, "bd_attn")
    if ms is None or ms <= 0 or ctx.get("rehearse"):  # another step's capture, a program without the scope, a CPU run
        return None
    adapter = ctx["adapter"]
    if not hasattr(adapter, "bd_attn_flops"):
        return None
    peaks = ctx["peaks"].lookup(ctx["device_kind"])
    bound_s = max(adapter.bd_attn_flops(ctx["shapes"], ctx["spec"]) / peaks["flops_per_s"],
                  adapter.bd_attn_bytes(ctx["shapes"], ctx["spec"]) / peaks["hbm_bytes_per_s"])
    return 100.0 * bound_s / (ms * 1e-3)
