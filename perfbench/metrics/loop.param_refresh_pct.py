"""Share of the traced window inside `Time/param_refresh`: the learner copying
the player's parameters to the player's device (and waiting for the burst
that still writes them)."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.span_share_pct(ctx, "Time/param_refresh")
