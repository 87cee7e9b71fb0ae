"""Share of the traced window in which the player is inside `Player/env_step`
(`envs.step(actions)`) AND the device is idle: the simulator's time that no
device work hides.

Read beside the act's split, so that the player's phases come from one
program: nothing to read where the capture holds no span of that name or no
`Player/dispatch` (a program older than the split).
"""
from perfbench import span_reduce

SPAN = "Player/env_step"


def read(ctx):
    cap = ctx.get("capture")
    if cap is None or not {SPAN, "Player/dispatch"} <= {ev[0] for ev in cap.host}:
        return None
    return span_reduce.span_idle_share_pct(ctx, SPAN)
