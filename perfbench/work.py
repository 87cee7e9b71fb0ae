"""Operations and bytes the algorithm needs, from shapes alone.

`train_step_flops` counts the matmuls and convs of one DreamerV3 gradient step
from the shapes of the weight leaves and the batch: forward 2 FLOP a
multiply-add, backward twice the forward (one product for the input's
gradient, one for the weight's). Recomputation, elementwise work, LayerNorm
and the losses are not counted. Which rows go through which weights:

* world-model loss, forward and backward, over T*B rows: encoder, RSSM
  (sequentially, T steps of B rows), decoder, reward and continue heads;
* imagination, forward only (for discrete actions every use of the imagined
  trajectory is behind a stop-gradient): the recurrent model and the
  transition head over horizon * T*B rows, the reward and continue heads and
  the critic over (horizon + 1) * T*B rows, the target critic over
  horizon * T*B rows;
* actor: forward over (horizon + 1) * T*B rows (counted once: the rollout's
  pass and the loss's pass are the same numbers), backward over the same;
* critic loss: backward over horizon * T*B rows (its forward is the one
  counted above).

`act_flops` counts the player's forward for one env step of one env: one row
through the encoder, the recurrent model, the posterior head and the actor
(what `make_player`'s step applies), 2 FLOP a multiply-add.

`gather_bytes` is what a [G, T, B] gather must move: every row read once from
the ring and written once into the batch.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np


def _macs(name: str, shape: Tuple[int, ...], image_side: int) -> Tuple[float, str]:
    """Multiply-adds for ONE row through this kernel, and the part it belongs to."""
    parts = name.split("/")
    if len(shape) == 2:
        return float(shape[0] * shape[1]), parts[1] if parts[0] == "wm" else parts[0]
    if len(shape) == 4:  # conv [kh, kw, in, out] / transposed conv [kh, kw, out, in]
        kh, kw, a, b = shape
        leaf = parts[-2]
        if leaf.startswith("conv_"):
            i = int(leaf.split("_")[1])
            out_side = image_side // (2 ** (i + 1))
            return float(out_side * out_side * kh * kw * a * b), "encoder"
        stages = 4
        i = stages - 1 if leaf == "to_obs" else int(leaf.split("_")[1])
        in_side = image_side // (2 ** (stages - i))
        return float(in_side * in_side * kh * kw * a * b), "observation_model"
    return 0.0, ""


def train_step_flops(shapes: Dict[str, Any], T: int, B: int, horizon: int, image_side: int = 64) -> Dict[str, float]:
    """FLOPs of one gradient step by part, and their sum under 'total'."""
    rows = T * B
    out: Dict[str, float] = {}

    def add(part: str, flops: float) -> None:
        out[part] = out.get(part, 0.0) + flops

    for name, (shape, _) in shapes.items():
        if not name.endswith("/kernel"):
            continue
        macs, part = _macs(name, tuple(shape), image_side)
        group = name.split("/")[0]
        if group == "wm":
            add(f"wm.{part}", 6.0 * macs * rows)  # forward + backward of the world-model loss
            if part == "rssm" and ("recurrent_model" in name or "transition" in name):
                add("imagination.rssm", 2.0 * macs * horizon * rows)
            if part in ("reward", "continue"):
                add("imagination.heads", 2.0 * macs * (horizon + 1) * rows)
        elif group == "actor":
            add("actor", 6.0 * macs * (horizon + 1) * rows)
        elif group == "critic":
            add("critic", 2.0 * macs * (horizon + 1) * rows + 4.0 * macs * horizon * rows)
        elif group == "target_critic":
            add("critic", 2.0 * macs * horizon * rows)
    out["total"] = float(sum(out.values()))
    return out


PLAYER_READS = ("wm/encoder/", "wm/rssm/recurrent_model/", "wm/rssm/representation/", "actor/")


def act_flops(shapes: Dict[str, Any], image_side: int = 64) -> float:
    """FLOPs of the player's forward for one env step: one row through every kernel it reads."""
    return float(sum(2.0 * _macs(name, tuple(shape), image_side)[0] for name, (shape, _) in shapes.items()
                     if name.endswith("/kernel") and name.startswith(PLAYER_READS)))


def row_bytes(batch_items: Dict[str, Tuple[Tuple[int, ...], Any]]) -> int:
    """Bytes of one replay row: every key's item shape and dtype."""
    return int(sum(int(np.prod(shape)) * np.dtype(dtype).itemsize for shape, dtype in batch_items.values()))


def gather_bytes(batch_items: Dict[str, Tuple[Tuple[int, ...], Any]], G: int, T: int, B: int) -> int:
    """Read each gathered row once, write it once, and read the int32 index of each."""
    return int(G * T * B * (2 * row_bytes(batch_items) + 4))


def ring_items(mix: Dict[str, Any], actions: int) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """The keys of one replay row for a mix: its observation keys, the reward
    as an observation where the mix says so, and the loop's own five."""
    items: Dict[str, Tuple[Tuple[int, ...], Any]] = {
        k: (tuple(v["shape"]), v["dtype"]) for k, v in mix["observation"].items()
    }
    if mix.get("reward_as_observation"):
        items["reward"] = ((1,), "float32")
    items["actions"] = ((int(actions),), "float32")
    for k in ("rewards", "terminated", "truncated", "is_first"):
        items[k] = ((1,), "float32")
    return items


def kept_bytes(shapes: Dict[str, Any], mix: Dict[str, Any], buffer_size: int, actions: int) -> Dict[str, float]:
    """What a cell keeps on the chip across calls: parameters (with the target
    critic), Adam's two moments for the trained groups, and the ring."""
    params = sum(int(np.prod(s)) * np.dtype(d).itemsize for s, d in shapes.values())
    trained = sum(int(np.prod(s)) * np.dtype(d).itemsize for n, (s, d) in shapes.items() if not n.startswith("target_critic/"))
    ring = buffer_size * int(mix["num_envs"]) * row_bytes(ring_items(mix, actions))
    return {"params": float(params), "adam": 2.0 * trained, "ring": float(ring), "total": float(params + 2 * trained + ring)}
