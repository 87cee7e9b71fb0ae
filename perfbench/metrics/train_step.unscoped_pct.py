"""Share of the summed op time of the step's programs in the traced window
under none of the step's `jax.named_scope` names (the cell's adapter's
`step_parts`, which the capture carries)."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.unscoped_pct(ctx)
