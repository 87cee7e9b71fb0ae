"""Device time, per gradient step, of the ops of the step's programs under the
`jax.named_scope` "mhc" (forward and backward), over their whole executions
in the traced window."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.part_ms(ctx, "mhc")
