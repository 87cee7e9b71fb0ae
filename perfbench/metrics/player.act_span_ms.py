"""Median duration of `Player/act` in the traced window: observation
preparation, the player's forward on its device and the fetch of the action,
timed inside the program (`player.act_ms` is the reading from outside)."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.span_median_ms(ctx, "Player/act")
