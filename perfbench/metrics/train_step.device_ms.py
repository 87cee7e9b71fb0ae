"""Device time of the step's programs (the adapter's `step_programs`) per
gradient step, over their whole executions in the traced window."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.step_ms(ctx)
