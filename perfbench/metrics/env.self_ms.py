"""Median time inside the synthetic env's own step(), all envs, in the window:
says whether the generator, not the system, set the pace."""
import numpy as np


def read(ctx):
    t0, t1 = ctx["window"]["t_open"], ctx["window"]["t_close"]
    vals = []
    for env in ctx["envs"].values():
        enter, self_s = np.asarray(env.t_enter), np.asarray(env.self_s)
        n = min(len(enter), len(self_s))
        keep = (enter[:n] >= t0) & (enter[:n] <= t1)
        vals.append(self_s[:n][keep])
    vals = np.concatenate(vals) if vals else np.zeros(0)
    return float(np.median(vals) * 1e3) if len(vals) else None
