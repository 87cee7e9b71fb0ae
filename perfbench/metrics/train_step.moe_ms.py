"""Device time, per gradient step, of the ops of the step's programs under the
`jax.named_scope` "moe" (the router, the grouping, the shared expert) and
under "experts" inside it (the grouped products), forward and backward, over
their whole executions in the traced window."""
from perfbench import span_reduce


def read(ctx):
    parts = [span_reduce.part_ms(ctx, p) for p in ("moe", "experts")]
    return None if all(p is None for p in parts) else sum(p for p in parts if p is not None)
