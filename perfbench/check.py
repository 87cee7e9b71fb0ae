"""What every comparison that decides `correct` shares.

The comparison itself belongs to an algorithm's family and lives in its
adapter (`perfbench/adapters/<name>.py:decide`): what the timed path itself
produced at the timed sizes, against that family's plain reference. It hands
back every number it read, the limits that follow from the cell's own
structure (a ratio, an exact row count), and the detail. Kept here is what is
common: the helpers for rows a generator names and for the worst leaf, the
limits file beside the configuration (`<home>/limits/<config>.json`, with
the readings they were set from in PERF.md), and the last step: each number
beside its limit. A number without a limit is printed in the detail only.
"""
from __future__ import annotations

import json
from typing import Any, Dict, List, Tuple

import numpy as np

QUEUE_SLACK_PACKETS = 16  # player runs at most queue_depth (4) + in-flight packets ahead


# -- the gathered rows of a replay ring ---------------------------------------------
def replay_rows(batches: List[Dict[str, np.ndarray]], envs: Dict[int, Any], image_key: str) -> Tuple[int, int, int]:
    """(rows looked at, rows that differ from what the generator emitted,
    columns whose rows are not consecutive emissions of one env)."""
    decode = type(next(iter(envs.values()))).decode  # the generator that emitted the rows reads its own stamp
    rows = wrong = broken = 0
    for batch in batches:
        T, B = batch["rewards"].shape[:2]
        for b in range(B):
            prev = None
            for t in range(T):
                rows += 1
                img = batch[image_key][t, b]
                e, n = decode(img)
                env = envs.get(e)
                if env is None or n >= env.n:
                    wrong += 1
                    prev = None
                    continue
                if prev is not None and (e, n) != (prev[0], prev[1] + 1):
                    broken += 1
                prev = (e, n)
                action = env.log_action[n]
                want_action = np.zeros_like(batch["actions"][t, b])
                if action is not None and not env.log_final[n]:
                    if want_action.shape[-1] == np.asarray(action).size and np.asarray(action).dtype.kind == "f":
                        want_action = np.asarray(action, np.float32).reshape(-1)
                    else:
                        want_action[int(np.asarray(action).reshape(-1)[0])] = 1.0
                final = env.log_final[n]
                ok = (
                    np.array_equal(img, env.frame(image_key, n))
                    and float(batch["rewards"][t, b, 0]) == np.float32(env.log_reward[n])
                    and ("reward" not in batch or float(batch["reward"][t, b, 0]) == np.float32(env.log_reward[n]))
                    and float(batch["terminated"][t, b, 0]) == float(env.log_terminated[n] and final)
                    and float(batch["truncated"][t, b, 0]) == float(env.log_truncated[n] and final)
                    and float(batch["is_first"][t, b, 0]) == float(env.log_first[n])
                    # the player stores its straight-through sample, one-hot to a float32 rounding
                    and (n == env.n - 1 or np.allclose(batch["actions"][t, b], want_action, rtol=0.0, atol=1e-6))
                )
                wrong += 0 if ok else 1
    return rows, wrong, broken


# -- gradient steps against env steps, where a replay ratio owes them -----------------
def ratio_numbers(run, envs: Dict[int, Any], mix: Dict[str, Any]) -> Dict[str, float]:
    ratio = float(mix["replay_ratio"])
    s0 = float(mix["_learning_starts"])
    exits = np.sort(np.concatenate([np.asarray(e.t_exit) for e in envs.values()]))
    calls_t = np.asarray(run.calls_t)
    k = np.cumsum(np.asarray(run.calls_g))
    n_env = np.searchsorted(exits, calls_t, side="right")
    enters = np.sort(np.concatenate([np.asarray(e.t_enter) for e in envs.values()]))
    n_started = np.searchsorted(enters, calls_t, side="right")
    due = s0 + k / ratio
    return {
        "ratio_early_steps": float(np.max(due - n_started)),
        "ratio_late_steps": float(np.max(n_env - due)),
    }


# -- the worst leaf -----------------------------------------------------------------------
def leaf_norms(tree_flat: Dict[str, Any]) -> Dict[str, float]:
    return {k: float(np.sqrt(np.sum(np.square(np.asarray(v, np.float64))))) for k, v in tree_flat.items()}


def gap_by_leaf(prog: Dict[str, float], ref: Dict[str, float], keep=None) -> Tuple[float, float, str]:
    """Worst and median over leaves of |prog - ref| / max(ref, median ref)."""
    names = [n for n in ref if keep is None or keep(n)]
    mid = float(np.median([ref[n] for n in names])) if names else 0.0
    gaps = {n: abs(prog[n] - ref[n]) / max(ref[n], mid, 1e-30) for n in names}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], float(np.median(list(gaps.values()))), worst


# -- each number beside its limit ---------------------------------------------------------
def load_limits(path: str) -> Dict[str, float]:
    with open(path) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def decide(run, envs: Dict[int, Any], spec: Dict[str, Any], adapter: Any) -> Tuple[Dict[str, Dict[str, Any]], Dict[str, Any]]:
    values, structural, detail = adapter.decide(run, envs, spec)
    limits = load_limits(spec["limits_file"])
    compared: Dict[str, Dict[str, Any]] = {}
    for name, value in values.items():
        limit = structural.get(name, limits.get(name))
        if limit is None:
            detail.setdefault("not_compared", {})[name] = value
            continue
        compared[name] = {"value": value, "limit": limit, "ok": bool(np.isfinite(value) and value <= limit)}
    return compared, detail
