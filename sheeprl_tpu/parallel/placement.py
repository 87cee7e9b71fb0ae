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
learner's first device, and ``auto`` decides by what it can observe, the
bytes of the tree the player reads: with the learner on an accelerator a
tree of at least ``AUTO_ACCELERATOR_MIN_BYTES`` stays on the learner's first
device, a smaller one goes to the CPU backend where the process has one;
with the learner on a CPU the player is on the learner's device.

Why bytes: a batch-1 forward reads every weight once, so on the host it is
bound by the host's memory and the mirror's device-to-host copy (with the
learner blocked and the device idle) grows with the tree, while on the chip
the forward costs 2-3 ms whatever the size. Measured on a v5e, the device
otherwise idle, float32, the leaves the DreamerV3 player reads; one act =
``prepare_obs`` + forward + fetch of the action, one refresh = the call
until the copy is done (PERF.md, PR 29):

============ ======== ============= ============= ================ ===============
DreamerV3    tree     act on cpu:0  act on tpu:0  refresh to cpu:0 refresh on tpu:0
============ ======== ============= ============= ================ ===============
S  (1 env)    32 MB    2.28 ms       2.21 ms        6.2 ms          3.4 ms
M  (1 env)    69 MB    3.46 ms       2.25 ms       11.7 ms          3.8 ms
L  (4 envs)  154 MB   10.47 ms       2.25 ms      115.3 ms          4.5 ms
XL (1 env)   432 MB   13.08 ms       2.85 ms      400.1 ms          5.6 ms
============ ======== ============= ============= ================ ===============

At S the two tie, and a host player keeps acting while a burst runs, which
a player on the learner's device cannot (its forward queues behind the
burst); from M on the host loses on every column. So the constant, 48 MiB,
lies between S and M. End to end on the two benchmark cells the chip gave
2.5 x (L) and 1.6 x (XL) the gradient steps a second of the host (PERF.md,
PR 29). A PPO or SAC MLP is kilobytes and acts on the host in microseconds
without a device round trip.

The mirror has two refresh modes (``algo.player.async_refresh``):

* ``blocking`` (default) — the next player step waits for the new weights:
  exactly the reference's always-latest-params semantics;
* ``async`` — the device→player copy is dispatched immediately but the
  player keeps using the previous weights until the new ones have landed
  (``jax.Array.is_ready``). Staleness is bounded by one transfer (a few env
  steps); standard practice in distributed actor–learner RL (IMPALA-family).
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.interpreters import partial_eval as pe

from ..telemetry.spans import Span

# `algo.player.device=auto` with the learner on an accelerator: a player whose
# parameter tree has at least this many bytes acts on the learner's first
# device, a smaller one on the host CPU backend (numbers: module docstring).
AUTO_ACCELERATOR_MIN_BYTES = 48 * 2**20


def host_device() -> Any:
    """The CPU backend device of this process. Raises ``RuntimeError`` where
    the process has none (e.g. ``JAX_PLATFORMS=tpu``)."""
    return jax.local_devices(backend="cpu")[0]


def tree_bytes(tree: Any) -> int:
    return sum(int(getattr(x, "nbytes", 0)) for x in jax.tree.leaves(tree))


def _mode(cfg: Any) -> str:
    return (cfg.select("algo.player.device", "auto") or "auto") if cfg is not None else "auto"


def player_device(cfg: Any, accelerator: Optional[Any] = None, player_bytes: Optional[int] = None) -> Any:
    """Resolve where per-step policy inference should run (see the module
    docstring for the three ``algo.player.device`` modes). ``player_bytes``
    is the size of the tree the player reads, which is what ``auto`` decides
    by on an accelerator; a caller that has no tree at hand leaves it out and
    gets the host."""
    mode = _mode(cfg)
    default = accelerator if accelerator is not None else jax.local_devices()[0]
    if mode == "accelerator":
        return default
    if mode == "host":
        return host_device()
    if mode != "auto":
        raise ValueError(f"algo.player.device must be auto|host|accelerator, got '{mode}'")
    if default.platform == "cpu":
        return default
    if player_bytes is not None and player_bytes >= AUTO_ACCELERATOR_MIN_BYTES:
        return default
    try:
        return host_device()
    except RuntimeError:  # accelerator-only process: auto stays on the learner's device
        return default


def _local_copy(x: Any, device: Any) -> Optional[Any]:
    """The copy of ``x`` that already lives on ``device`` (the whole array on
    a single-device run, this device's copy of a leaf replicated over the
    mesh), or None where it has none there."""
    if isinstance(x, jax.Array) and x.is_fully_replicated and device in x.devices():
        return next(s.data for s in x.addressable_shards if s.device == device)
    return None


@jax.jit
def _copy_leaves(leaves):
    return [jnp.copy(x) for x in leaves]


class ParamMirror:
    """Player-side copy of (a subtree of) the learner params.

    ``refresh(new)`` copies them to the mirror's device, in one of two ways,
    leaf by leaf. A leaf that already has a copy on that device (player and
    learner share it) is copied there by ONE jitted program over all such
    leaves: the call dispatches it and returns futures, the device's stream
    runs it after the burst that writes the parameters and before the next
    burst, which donates them, and nothing waits (``same_device`` 1 on
    ``Time/param_refresh``, which then times a dispatch). Any other leaf
    goes through ``device_put``; between two backends (learner on the TPU,
    player on ``cpu:0``) that fetches it to the host first, so the call
    returns only when the burst has ended and the copy is done.
    ``current()`` returns the params the player should use
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

    def __init__(self, params: Any, device: Any, async_refresh: bool = False, in_order: bool = False):
        self.device = device
        self.async_refresh = bool(async_refresh)
        # the caller's word that no act is dispatched between an update's dispatch and the refresh that follows it:
        # a blocking mirror on the learner's device then holds the learner's own buffers (`_put`)
        self.alias = bool(in_order) and not self.async_refresh
        self.same_device = False  # whether the newest copy never left the device
        self.aliased = False  # whether the newest "copy" is the learner's own buffers
        self.copied_bytes = 0  # what the newest refresh copied or moved
        self.params = self._put(params)
        self._pending: Optional[Any] = None
        self._swap_lock = threading.Lock()
        self.placement: Optional[Dict[str, Any]] = None  # the run's `placement` event, from `make_param_mirror`

    def _put(self, params: Any) -> Any:
        """Copy params to the mirror device, every leaf committed to that ONE
        device (`SingleDeviceSharding`): the player's key and recurrent state
        are single-device arrays too, and a leaf that kept the learner's
        mesh sharding would give the player step's inputs two different
        abstract meshes — its key comes back from the first call under the
        params' mesh and the second call retraces.

        ``device_put`` ALIASES a buffer that already lives on the target
        device, and the learner's train step donates its param buffers,
        which would delete the mirror's copy out from under the player.
        Those leaves get a real on-device copy: new buffers, all from one
        dispatch.

        The one exception is a mirror told that acting and the update are in
        order on one stream (``in_order``, never with async refresh): with
        every leaf on its device it keeps each leaf's single-device view of
        the learner's own buffer (`_local_copy`: committed to the one device
        like a copy would be, so the player step traces the same), allocates
        nothing, and is re-pointed by the refresh that follows the donating
        update. A model whose parameters fill most of the chip has no room
        for a second copy, let alone a third in flight."""
        leaves, treedef = jax.tree.flatten(params)
        local = [_local_copy(x, self.device) for x in leaves]
        here = [x for x in local if x is not None]
        self.same_device = len(here) == len(leaves)
        self.aliased = self.alias and self.same_device
        self.copied_bytes = 0 if self.aliased else tree_bytes(leaves)
        if self.aliased:
            return treedef.unflatten(local)
        copied = iter(_copy_leaves(here) if here else ())
        return treedef.unflatten(
            [jax.device_put(x, self.device) if here is None else next(copied) for x, here in zip(leaves, local)]
        )

    def refresh(self, params: Any) -> None:
        leaves = jax.tree.leaves(params)
        with Span("Time/param_refresh", leaves=len(leaves)) as span:
            new = self._put(params)
            span.count(bytes=self.copied_bytes, same_device=int(self.same_device))
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


def read_subtree(tree: Any, probe: Callable[..., Any], *args: Any) -> Callable[[Any], Any]:
    """Which leaves of ``tree`` does ``probe(tree, *args)`` read? Traces the
    probe once (nothing runs; ``args`` may be ``jax.ShapeDtypeStruct``s), drops
    what its outputs do not depend on, through nested ``jit`` calls too, and
    returns ``select``: ``select(other)`` holds the leaves of ``other`` (any
    nested dict with ``tree``'s paths in it) that were read, and none of the
    dicts that leaves empty. A player whose ``probe`` makes every call the
    env loop makes can then mirror ``select(params)`` and act bit-identically:
    the leaves left out enter none of its programs."""
    from ..telemetry import xla as _xla

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    with _xla.suppress_retrace_accounting():  # a diagnostic trace, not the loop's
        closed = jax.make_jaxpr(probe)(tree, *args)
    _, used = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))
    read = [tuple(k.key for k in path) for (path, _), u in zip(flat, used) if u]  # tree's leaves come first

    def select(other: Any) -> Any:
        out: Dict[str, Any] = {}
        for path in read:
            src, dst = other, out
            for k in path[:-1]:
                src, dst = src[k], dst.setdefault(k, {})
            dst[path[-1]] = src[path[-1]]
        return out

    return select


def make_param_mirror(cfg: Any, accelerator: Any, params: Any, root_key: Any, allow_async: bool = True, in_order: bool = False):
    """The per-algorithm player setup, in one place: resolve the player
    device from the bytes of ``params`` (the tree the player reads), mirror
    it there, and derive a player PRNG key committed next to it (so the env
    loop never does a host-side split).

    ``allow_async=False`` pins the mirror to blocking refresh regardless of
    ``algo.player.async_refresh`` — on-policy algorithms (PPO/A2C) must act
    with the params the coming update will be credited to.

    ``in_order=True`` is the caller's word that acting and the update are
    serial on one in-order stream: no act is dispatched between an update's
    dispatch and the ``refresh`` that follows it (a loop with no player
    thread; never the threaded source of ``engine/overlap.py``, whose player
    acts beside the update). A blocking mirror on the learner's device then
    aliases the learner's buffers instead of copying them.

    ``mirror.placement`` is the choice as the run's ``placement`` event; the
    caller emits it once it has its ``telem``.

    Returns ``(mirror, pdev, player_key, root_key)`` — the new ``root_key``
    replaces the caller's (one split is consumed).
    """
    nbytes = tree_bytes(params)
    pdev = player_device(cfg, accelerator, nbytes)
    mirror = ParamMirror(
        params,
        pdev,
        async_refresh=allow_async and bool(cfg.select("algo.player.async_refresh", False)),
        in_order=in_order and not allow_async,
    )
    mirror.placement = {
        "event": "placement",
        "player_device": f"{pdev.platform}:{pdev.id}",
        "learner_device": f"{accelerator.platform}:{accelerator.id}",
        "mode": str(_mode(cfg)),
        "tree_bytes": nbytes,
        "threshold_bytes": AUTO_ACCELERATOR_MIN_BYTES,
        "same_device": int(mirror.same_device),
        # how a refresh brings the new parameters: the learner's own buffers re-pointed (acting and the update in
        # order on one device), one on-device copy, or a transfer to the player's device
        "refresh": "alias" if mirror.aliased else "copy" if mirror.same_device else "transfer",
    }
    root_key, pk = jax.random.split(root_key)
    return mirror, pdev, jax.device_put(pk, pdev), root_key
