"""Shared helpers: replay-ratio controller, schedules, config printing.

`Ratio` reproduces the reference's gradient-steps/policy-steps controller
(sheeprl/utils/utils.py:259-300). Numeric transforms (symlog, two-hot, GAE)
live in `sheeprl_tpu.ops` because on TPU they are jitted device code.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional

import numpy as np


class Ratio:
    """Replay-ratio controller: how many gradient steps to run for the env
    steps taken since the last update (reference utils.py:259-300)."""

    def __init__(self, ratio: float, pretrain_steps: int = 0):
        if pretrain_steps < 0:
            raise ValueError(f"'pretrain_steps' must be non-negative, got {pretrain_steps}")
        if ratio < 0:
            raise ValueError(f"'ratio' must be non-negative, got {ratio}")
        self._pretrain_steps = pretrain_steps
        self._ratio = ratio
        self._prev: Optional[float] = None

    def __call__(self, step: float) -> int:
        if self._ratio == 0:
            return 0
        if self._prev is None:
            self._prev = step
            repeats = int(self._pretrain_steps * self._ratio)
            if self._pretrain_steps > 0 and repeats == 0:
                repeats = 1
            return repeats
        repeats = round((step - self._prev) * self._ratio)
        self._prev += repeats / self._ratio
        return int(repeats)

    def peek(self, step: float) -> int:
        """Predict what `__call__(step)` would return, without consuming the
        budget — used to stage the next replay batch while the device is busy
        (the controller is deterministic, so the prediction is exact)."""
        if self._ratio == 0:
            return 0
        if self._prev is None:
            repeats = int(self._pretrain_steps * self._ratio)
            if self._pretrain_steps > 0 and repeats == 0:
                repeats = 1
            return repeats
        return int(round((step - self._prev) * self._ratio))

    def state_dict(self) -> Dict[str, Any]:
        return {"_ratio": self._ratio, "_prev": self._prev, "_pretrain_steps": self._pretrain_steps}

    def load_state_dict(self, state: Dict[str, Any]) -> "Ratio":
        self._ratio = float(state["_ratio"])
        self._prev = state["_prev"]
        self._pretrain_steps = int(state["_pretrain_steps"])
        return self


def linear_annealing(initial: float, step: int, total_steps: int, final: float = 0.0) -> float:
    """LR / clip-coef annealing (reference ppo.py:414-424 uses torch scheds)."""
    frac = min(max(step / max(total_steps, 1), 0.0), 1.0)
    return initial + frac * (final - initial)


def print_config(cfg: Any) -> None:
    """Rich tree dump of the composed config (reference utils.py:208-237)."""
    import yaml

    try:
        from rich.console import Console
        from rich.syntax import Syntax

        Console().print(Syntax(yaml.safe_dump(cfg.to_dict(), sort_keys=False), "yaml"))
    except Exception:
        print(yaml.safe_dump(cfg.to_dict(), sort_keys=False))


def save_configs(cfg: Any, log_dir: str) -> None:
    from ..config import save_config

    save_config(cfg, f"{log_dir}/config.yaml")


# <checkout>/.xla_cache (git-ignored): a fixed path, because the directory
# is part of every cache key — a cache that moves never hits
DEFAULT_XLA_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), ".xla_cache"
)


def enable_compilation_cache() -> None:
    """Persistent XLA compilation cache (the DreamerV3 train program takes
    most of a minute to compile for a TPU). Where `JAX_COMPILATION_CACHE_DIR`
    is set, JAX already keeps its cache there and no other directory is set
    in code; otherwise the cache lives in `DEFAULT_XLA_CACHE_DIR`.
    `SHEEPRL_NO_COMPILATION_CACHE=1` disables it. Safe to call repeatedly.

    On an accelerator the ops' metadata is part of the key. JAX leaves it out
    by default, and an executable loaded from the cache keeps the metadata it
    was compiled with: a capture would then show the `jax.named_scope` names
    (and source lines) of whatever checkout compiled it first, and a reading
    of `jit(train)` by part would book ops to scopes that have moved. The
    price is a compile whenever a line of the traced code moves. A process
    held to the CPU (`JAX_PLATFORMS=cpu`) keeps JAX's default: nothing reads
    its op names. That is read from the configuration, because asking for the
    backend here would initialise it before `jax.distributed.initialize`."""
    if os.environ.get("SHEEPRL_NO_COMPILATION_CACHE"):
        return
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_XLA_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    if (jax.config.jax_platforms or "").split(",")[0] != "cpu":
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def acknowledge_partial_donation() -> None:
    """Donating the replay batch to a scanned train step intentionally
    includes leaves XLA cannot alias (uint8 frames, tiny flag columns) —
    the big float leaves DO donate, and jax warns once per compile about
    the rest. Expected, not actionable: silence exactly that message."""
    import warnings

    warnings.filterwarnings("ignore", message="Some donated buffers were not usable")


def unwrap_fabric(obj: Any) -> Any:  # parity shim; no wrapping exists here
    return obj


def dotdict(d: Any) -> Any:
    from ..config import Config

    return Config(d) if not isinstance(d, Config) else d


class WallClockStopper:
    """`algo.max_wall_time_s` support: stop training cleanly at a step
    boundary once the wall-clock budget is spent (bench legs running under an
    external kill budget report SPS over the steps that actually ran).

    Single-host only: each process consults its own clock, so under
    multi-host SPMD one rank could break out while another enters a
    cross-host collective and deadlock — the knob is ignored (with a
    warning) when `jax.process_count() > 1`.
    """

    def __init__(self, cfg: Any):
        import sys
        import time

        import jax

        self.max_s = float(cfg.select("algo.max_wall_time_s", -1) or -1)
        if self.max_s > 0 and jax.process_count() > 1:
            print(
                "[wall-time] algo.max_wall_time_s ignored: rank-local clocks can't "
                "coordinate a multi-host stop (use total_steps)",
                file=sys.stderr,
            )
            self.max_s = -1.0
        self._t0 = time.perf_counter()

    def expired(self, policy_step: int, total_steps: int) -> bool:
        import sys
        import time

        if self.max_s <= 0:
            return False
        elapsed = time.perf_counter() - self._t0
        if elapsed <= self.max_s:
            return False
        print(
            f"[wall-time] stopping at step {policy_step}/{total_steps} after {elapsed:.1f}s",
            file=sys.stderr,
            flush=True,
        )
        return True


def wall_cap_reached(
    wall: "WallClockStopper", policy_step: int, total_steps: int, ckpt, state_fn, cfg, save: bool = True
) -> bool:
    """Shared wall-cap stop policy for training loops: when the budget is
    spent, write the final checkpoint (iff `checkpoint.save_last` — the knob
    that means "checkpoint on exit"), record where the run actually stopped
    for in-process callers (utils/run_info.py — the bench computes SPS over
    the steps that really ran), and tell the caller to break. ``save=False``
    defers the final checkpoint to a caller-owned exit path (decoupled SAC
    saves after the player thread has joined)."""
    if not wall.expired(policy_step, total_steps):
        return False
    if save and cfg.checkpoint.save_last:
        ckpt.save(policy_step, state_fn())
    from . import run_info

    run_info.last_run.update(policy_step=policy_step, total_steps=total_steps, wall_capped=True)
    return True
