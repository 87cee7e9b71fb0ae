"""Device time, per gradient step, of the ops of the step's programs under the
`jax.named_scope` "bd_attn" (a block-diffusion model's two-part attention:
its norm, projections, both parts of the scores under one softmax and the
output projection), forward, recompute and backward, over their whole
executions in the traced window."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.part_ms(ctx, "bd_attn")
