"""Actor/learner placement: which device runs per-step policy inference.

The reference runs player and trainer on the same torch device (e.g.
sheeprl/algos/dreamer_v3/dreamer_v3.py builds PlayerDV3 on ``fabric.device``).
Here the two are placed separately. The per-env-step policy forward of a small
net is microseconds of compute, and every step also pays a dispatch and a
device→host fetch of the action before the environment can move; the gradient
step is the large fused program. So the loop is split (Podracer/Sebulba-style
actor–learner placement, re-derived for a single-controller JAX process):

* the **learner** (the big fused gradient-step program) stays on the
  accelerator mesh, fed by the replay prefetcher;
* the **player** (per-step policy inference + recurrent state) runs wherever
  its params are committed — same weights, same jitted code, compiled for
  that device simply by committing its inputs there;
* a :class:`ParamMirror` keeps the player's copy of the weights in sync,
  refreshed after every train burst (parameters only change there).

``algo.player.device`` picks the player's device: ``host`` is the CPU backend
of this process (an error where the process has none), ``accelerator`` the
learner's first device, and ``auto`` the CPU backend whenever the learner is
an accelerator and the process has one, else the learner's device. Which of
``host`` and ``accelerator`` is faster on a locally attached chip is not
measured (ROADMAP, Speed item 4); ``chip_smoke.py`` prints where the player
ran.

The mirror has two refresh modes (``algo.player.async_refresh``):

* ``blocking`` (default) — the next player step waits for the new weights:
  exactly the reference's always-latest-params semantics;
* ``async`` — the device→player copy is dispatched immediately but the
  player keeps using the previous weights until the new ones have landed
  (``jax.Array.is_ready``). Staleness is bounded by one transfer (a few env
  steps); standard practice in distributed actor–learner RL (IMPALA-family).
"""
from __future__ import annotations

from typing import Any, Optional

import jax

from ..telemetry.spans import Span


def host_device() -> Any:
    """The CPU backend device of this process. Raises ``RuntimeError`` where
    the process has none (e.g. ``JAX_PLATFORMS=tpu``)."""
    return jax.local_devices(backend="cpu")[0]


def player_device(cfg: Any, accelerator: Optional[Any] = None) -> Any:
    """Resolve where per-step policy inference should run (see the module
    docstring for the three ``algo.player.device`` modes)."""
    mode = "auto"
    if cfg is not None:
        mode = cfg.select("algo.player.device", "auto") or "auto"
    default = accelerator if accelerator is not None else jax.local_devices()[0]
    if mode == "accelerator":
        return default
    if mode == "host":
        return host_device()
    if mode != "auto":
        raise ValueError(f"algo.player.device must be auto|host|accelerator, got '{mode}'")
    if default.platform == "cpu":
        return default
    try:
        return host_device()
    except RuntimeError:  # accelerator-only process: auto stays on the learner's device
        return default


class ParamMirror:
    """Player-side copy of (a subtree of) the learner params.

    ``refresh(new)`` copies them to the mirror's device. Between two backends
    (learner on the TPU, player on ``cpu:0``) ``device_put`` fetches each
    leaf to the host first, so the call returns only when the burst that
    writes the params has ended and the copy is done: ``Time/param_refresh``
    times it (0.8 s for 0.82 GB after two DreamerV3-XL bursts on a v5e,
    PERF.md). ``current()`` returns the params the player should use
    this step. In blocking mode that is always the newest copy (the player
    step then waits on the transfer); in async mode the newest copy is
    swapped in only once every leaf ``is_ready()``, so the player never
    stalls on the transfer.

    Thread contract (the overlap engine, ``engine/overlap.py``, relies on
    it): ``refresh`` is called by the learner thread, ``current`` by the
    player thread. Both only ever swap whole-pytree references, and the
    pending-slot handoff is guarded by a tiny lock (uncontended in serial
    loops; taken once per env step / per burst, never on the device hot
    path), so a refresh landing mid-swap can never be dropped and the
    player never sees a half-updated tree.
    """

    def __init__(self, params: Any, device: Any, async_refresh: bool = False):
        import threading

        self.device = device
        self.async_refresh = bool(async_refresh)
        self.params = self._put(params)
        self._pending: Optional[Any] = None
        self._swap_lock = threading.Lock()

    def _put(self, params: Any) -> Any:
        """Copy params to the mirror device, every leaf committed to that ONE
        device (`SingleDeviceSharding`): the player's key and recurrent state
        are single-device arrays too, and a leaf that kept the learner's
        mesh sharding would give the player step's inputs two different
        abstract meshes — its key comes back from the first call under the
        params' mesh and the second call retraces.

        ``device_put`` ALIASES a buffer that already lives on the target
        device — the whole array on a single-device run where learner and
        player share the device, or this device's copy of a leaf replicated
        over the mesh — and the learner's train step donates its param
        buffers, which would delete the mirror's copy out from under the
        player. Those leaves get a real on-device copy first."""

        def put_leaf(x: Any) -> Any:
            if isinstance(x, jax.Array) and x.is_fully_replicated and self.device in x.devices():
                import jax.numpy as jnp

                local = next(s.data for s in x.addressable_shards if s.device == self.device)
                x = jnp.copy(local)  # new buffer on the same device
            return jax.device_put(x, self.device)

        return jax.tree.map(put_leaf, params)

    def refresh(self, params: Any) -> None:
        leaves = jax.tree.leaves(params)
        nbytes = sum(int(getattr(x, "nbytes", 0)) for x in leaves)
        with Span("Time/param_refresh", bytes=nbytes, leaves=len(leaves)):
            new = self._put(params)
        if self.async_refresh:
            with self._swap_lock:
                self._pending = new
        else:
            self.params = new

    def current(self) -> Any:
        pending = self._pending  # racy peek is fine: the swap below re-checks
        if pending is not None:
            try:
                ready = all(x.is_ready() for x in jax.tree.leaves(pending))
            except AttributeError:  # non-Array leaves: treat as ready
                ready = True
            if ready:
                # locked swap: a refresh() landing between the peek and here
                # must not be clobbered with None (it would be lost forever)
                with self._swap_lock:
                    self.params = pending
                    if self._pending is pending:
                        self._pending = None
        return self.params


def place_for_inference(cfg: Any, params: Any) -> Any:
    """One-shot placement for evaluation rollouts: commit a params subtree to
    the player device (the same choice as the training players). Feed the
    jitted policy NUMPY inputs so every step runs on this device."""
    return jax.device_put(params, player_device(cfg))


def make_param_mirror(cfg: Any, accelerator: Any, params: Any, root_key: Any, allow_async: bool = True):
    """The per-algorithm player setup, in one place: resolve the player
    device, mirror the player's param subtree there, and derive a player PRNG
    key committed next to it (so the env loop never does a host-side split).

    ``allow_async=False`` pins the mirror to blocking refresh regardless of
    ``algo.player.async_refresh`` — on-policy algorithms (PPO/A2C) must act
    with the params the coming update will be credited to.

    Returns ``(mirror, pdev, player_key, root_key)`` — the new ``root_key``
    replaces the caller's (one split is consumed).
    """
    pdev = player_device(cfg, accelerator)
    mirror = ParamMirror(
        params,
        pdev,
        async_refresh=allow_async and bool(cfg.select("algo.player.async_refresh", False)),
    )
    root_key, pk = jax.random.split(root_key)
    return mirror, pdev, jax.device_put(pk, pdev), root_key
