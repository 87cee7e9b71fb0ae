"""Share of the traced window in which the player is inside `Player/fetch`
(every `np.asarray` of the act's outputs) AND the device is idle: the copy
of the action to the host and the host's wake-up after the device ends, while
nothing runs. (The part of a fetch that waits for the forward, or for a burst
ahead of it, has the device busy and is not in it.)

Read beside the act's split, so that the player's phases come from one
program: nothing to read where the capture holds no span of that name or no
`Player/dispatch` (a program older than the split).
"""
from perfbench import span_reduce

SPAN = "Player/fetch"


def read(ctx):
    cap = ctx.get("capture")
    if cap is None or not {SPAN, "Player/dispatch"} <= {ev[0] for ev in cap.host}:
        return None
    return span_reduce.span_idle_share_pct(ctx, SPAN)
