"""Median duration of `Player/dispatch` in the traced window: `mirror.current()`
and the act's jitted call until it returns. Unlike `player.act_span_ms` it
does not mix the act that waits behind a burst in its fetch with the one that
does not.

Read beside the act's split, so that the player's phases come from one
program: nothing to read where the capture holds no span of that name or no
`Player/dispatch` (a program older than the split).
"""
from perfbench import span_reduce

SPAN = "Player/dispatch"


def read(ctx):
    cap = ctx.get("capture")
    if cap is None or not {SPAN, "Player/dispatch"} <= {ev[0] for ev in cap.host}:
        return None
    return span_reduce.span_median_ms(ctx, SPAN)
