"""Median time between env 0's step() returning and its next step() call, in
the window: acting, replay-row bookkeeping and whatever the player waits for."""
import numpy as np


def read(ctx):
    env = ctx["envs"][0]
    t0 = ctx["window"]["t_open"], ctx["window"]["t_close"]
    enter, exit_ = np.asarray(env.t_enter), np.asarray(env.t_exit)
    n = min(len(enter) - 1, len(exit_))
    if n <= 0:
        return None
    waits = enter[1 : n + 1] - exit_[:n]
    keep = (exit_[:n] >= t0[0]) & (enter[1 : n + 1] <= t0[1])
    if not keep.any():
        return None
    return float(np.median(waits[keep]) * 1e3)
