"""Device time, per gradient step, of the ops of jit(train) under the
`jax.named_scope` "wm_rssm" (forward and backward) in the traced window."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.part_ms("wm_rssm", ctx["window"]["grad_steps"])
