"""Device-resident replay ring: stream rows once, sample in HBM.

The StagedPrefetcher ships every sampled batch host→HBM: a DreamerV3 burst
batch — 16 seq × 64 steps of 64×64×3 uint8 frames — is 12.6 MB per gradient
step, sampled and copied by the host while the device waits for it.

Every sampled batch is a gather from rows the host already sent before, so a
transition can cross to the device **once**, when it is added, instead of
once per sampled batch. This module keeps a device-side mirror of the
sequential replay buffer (whether it beats host staging on a locally
attached chip is not measured: ROADMAP, Design item 3):

* ``ring[key]`` is a ``[buffer_size, n_envs, *stored item]`` jax.Array in HBM
  indexed exactly like the host :class:`EnvIndependentReplayBuffer` (env
  ``e``'s sub-buffer row ``t`` lives at ``ring[key][t, e]``), dtypes preserved
  (rgb stays uint8 — 4× fewer bytes than f32 in the transfer *and* in HBM);
* how a row's item is stored (:func:`stored_item_shape`, the one place that
  knows): an item whose element count is a whole number of the TPU's native
  tiles (8 sublanes of 32 bits × 128 lanes: 4096 uint8, 1024 float32) is kept
  as ``[elements // 128, 128]``, every other item in its own shape. A TPU
  tiles the two minor-most dimensions of an array, and for
  ``u8[rows, n_envs, 64, 64, 3]`` a minor dimension of 3 would pad 42×, so
  XLA's default layout made the ROW axis minor-most: one 12288-byte row lay
  strewn over the whole buffer, and every gather copied the ring into a
  row-major layout first and every scatter copied it there and back (83 ms
  and 114 ms a gradient step at 2.7 and 3.7 GB: PERF.md, PR 33). Stored as
  ``u8[rows, n_envs, 96, 128]`` the same bytes tile exactly, the default
  layout is row-major, and both programs touch only the rows they name.
  The gather reshapes back to the item's shape inside its own program, so a
  batch is shape for shape and bit for bit what the host buffer would give;
* ``sync()`` ships only the rows added since the last sync — ``O(new
  transitions)``, a few KB per burst — and scatters them into the ring with
  a donated jitted update (index vectors padded to a fixed bucket so the
  program never recompiles; padding rows carry out-of-range indices and are
  dropped by ``mode="drop"``);
* sampling draws window starts on the host with the *same* index math as
  the host buffer (``SequentialReplayBuffer.sample_starts`` — the host
  buffer stays the source of truth for checkpoint/resume and validity
  rules), ships the tiny ``[G, T, B]`` index arrays, and gathers the
  training batch entirely on device.

The host buffer remains authoritative: checkpointing, restart surgery
(``mark_restart`` rewrites flags in rows that may already be mirrored — see
``_dirty_rows``) and resume all go through it; ``resync()`` rebuilds the
ring from host state after a checkpoint load.

The class is a drop-in for ``StagedPrefetcher`` (same ``stage(g)`` /
``take(g)`` contract) on the sequential-replay path used by the
DreamerV1/V2/V3 and Plan2Explore training loops; :func:`make_sequential_prefetcher`
picks the implementation per run (``buffer.device_cache``: auto | true |
false — auto enables the ring when the mesh is a single non-CPU device and
the buffer fits ``buffer.device_cache_max_bytes``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry.spans import Span
from .buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer
from .prefetch import StagedPrefetcher


_LANES = 128  # a TPU tile is 8 sublanes of 32 bits by 128 lanes


def stored_item_shape(item: Sequence[int], dtype: Any) -> Tuple[int, ...]:
    """Shape in which the ring keeps one row's item of logical shape ``item``:
    ``[elements // 128, 128]`` where the elements fill whole native tiles (the
    leaf's default TPU layout is then row-major and unpadded: module
    docstring), else ``item`` itself. Reads shape and dtype, nothing else."""
    item = tuple(int(d) for d in item)
    n = int(np.prod(item, dtype=np.int64))
    tile = _LANES * 8 * max(1, 4 // np.dtype(dtype).itemsize)
    return (n // _LANES, _LANES) if n and n % tile == 0 else item


def as_stored(x: Any, item: Sequence[int]) -> Any:
    """``x[*lead, *item]`` (numpy or jax) in the shape the ring stores it."""
    item = tuple(item)
    return x.reshape(x.shape[: x.ndim - len(item)] + stored_item_shape(item, x.dtype))


def as_logical(x: Any, item: Sequence[int]) -> Any:
    """The inverse of :func:`as_stored`: a stored leaf or gathered batch back
    in its item's own shape."""
    item = tuple(item)
    return x.reshape(x.shape[: x.ndim - len(stored_item_shape(item, x.dtype))] + item)


# (key, logical item shape) of the leaves stored in another shape than their
# own: the static argument that tells a gather what to restore
_Items = Tuple[Tuple[str, Tuple[int, ...]], ...]


def _allocate(
    host: Any, size: int, n_envs: int, device: Any, emit: Optional[Any]
) -> Tuple[Dict[str, jax.Array], _Items]:
    """Zeroed ring leaves on ``device`` for a host buffer whose leaves are
    ``[size, n_envs, *item]``, each in its stored shape, and the gathers'
    ``items`` argument; the run's ``ring_layout`` event goes to ``emit``
    (``telem.emit``) where one is given."""
    layout: Dict[str, Any] = {}
    ring: Dict[str, jax.Array] = {}
    items = []
    total = contiguous = 0
    for k in host.keys():
        item, dtype = tuple(host[k].shape[2:]), host[k].dtype
        stored = stored_item_shape(item, dtype)
        ring[k] = jax.device_put(jnp.zeros((size, n_envs) + stored, dtype=dtype), device)
        nbytes = ring[k].nbytes
        layout[k] = {"logical": list(item), "stored": list(stored), "dtype": str(np.dtype(dtype)), "bytes": nbytes}
        total += nbytes
        if stored != item:
            items.append((k, item))
            contiguous += nbytes
    if emit is not None:
        emit(
            {
                "event": "ring_layout",
                "device": f"{device.platform}:{device.id}",
                "rows": size,
                "n_envs": n_envs,
                "keys": layout,
                "total_bytes": total,
                "contiguous_bytes": contiguous,
                "contiguous_bytes_share": contiguous / total if total else 0.0,
            }
        )
    return ring, tuple(items)


@functools.partial(jax.jit, donate_argnums=0)
def _scatter_rows(ring: Dict[str, jax.Array], rows: Dict[str, jax.Array],
                  t_idx: jax.Array, e_idx: jax.Array) -> Dict[str, jax.Array]:
    # rows[k] is [n, *item], stored or logical: written in the ring's own item
    # shape. Padding entries carry t_idx == buffer_size → dropped, not clipped
    return {
        k: ring[k].at[t_idx, e_idx].set(rows[k].reshape(rows[k].shape[:1] + ring[k].shape[2:]), mode="drop")
        for k in ring
    }


@functools.partial(jax.jit, static_argnames=("f32_keys", "items"))
def _gather_batch(ring: Dict[str, jax.Array], t_idx: jax.Array, e_idx: jax.Array,
                  f32_keys: Tuple[str, ...], items: _Items = ()) -> Dict[str, jax.Array]:
    # t_idx [G, L, B] with e_idx [B] broadcasts to [G, L, B, *stored item];
    # `items` restores the item's own shape on the batch alone
    out = {k: ring[k][t_idx, e_idx] for k in ring}
    for k, item in items:
        out[k] = as_logical(out[k], item)
    return {k: v.astype(jnp.float32) if k in f32_keys else v for k, v in out.items()}


class _StagedGather:
    """The one-iteration-ahead ``stage``/``take`` contract shared by every
    ring variant, over an abstract ``_gather(g)``: ``stage`` dispatches the
    next batch (swallowing not-enough-data errors), ``take`` returns the
    staged batch on a ``g`` match or gathers fresh."""

    _staged: Optional[tuple] = None

    def stage(self, g: int) -> None:
        if g <= 0:
            self._staged = None
            return
        try:
            self._staged = (g, self._gather(g))
        except (ValueError, RuntimeError):
            self._staged = None

    def take(self, g: int) -> Any:
        staged, self._staged = self._staged, None
        if staged is not None and staged[0] == g:
            return staged[1]
        return self._gather(g)


class DeviceRingPrefetcher(_StagedGather):
    """``stage``/``take`` prefetcher serving training batches from an HBM
    mirror of an ``EnvIndependentReplayBuffer`` of sequential sub-buffers."""

    def __init__(
        self,
        rb: EnvIndependentReplayBuffer,
        batch_size: int,
        sequence_length: int,
        cnn_keys: Sequence[str] = (),
        device: Optional[Any] = None,
        bucket: int = 8,
        emit: Optional[Any] = None,
    ):
        for b in rb.buffer:
            if not isinstance(b, SequentialReplayBuffer):
                raise TypeError(
                    "DeviceRingPrefetcher mirrors sequential sub-buffers, got "
                    f"{type(b).__name__}"
                )
        self._rb = rb
        self._batch = int(batch_size)
        self._seq = int(sequence_length)
        self._cnn_keys = tuple(cnn_keys)
        self._device = device if device is not None else jax.local_devices()[0]
        self._bucket = int(bucket)
        self._emit = emit  # telem.emit: takes the `ring_layout` event at allocation
        self._ring: Optional[Dict[str, jax.Array]] = None
        self._items: _Items = ()  # the leaves stored in another shape than their own
        # per-env monotonic added-row count at the last sync (sub-buffer
        # _added never wraps, so a >= buffer_size backlog is detectable)
        self._synced_added: List[int] = [0] * rb.n_envs
        self._staged: Optional[tuple] = None  # (g, device_batch)
        self._last_idx: Optional[tuple] = None  # (t_idx, env_order) — tests
        self._dirty_rows: List[tuple] = []  # (env, row) host edits to re-ship

    # -- host-side bookkeeping --------------------------------------------
    @property
    def ring(self) -> Optional[Dict[str, jax.Array]]:
        return self._ring

    def mark_dirty(self, env_idx: int, row: int) -> None:
        """Re-ship a row the host edited in place (restart surgery rewrites
        terminated/truncated/is_first flags of an already-mirrored row)."""
        self._dirty_rows.append((int(env_idx), int(row) % self._rb.buffer_size))

    def _ensure_ring(self) -> None:
        if self._ring is not None:
            return
        proto = self._rb.buffer[0]
        if proto.empty:
            raise ValueError("No data in the buffer, cannot mirror")
        self._ring, self._items = _allocate(
            proto, self._rb.buffer_size, self._rb.n_envs, self._device, self._emit
        )

    def _pending_rows(self) -> List[Tuple[int, int]]:
        """(env, row) pairs added or edited since the last sync, oldest
        first per env."""
        rows: List[Tuple[int, int]] = []
        size = self._rb.buffer_size
        for e, b in enumerate(self._rb.buffer):
            if b.empty:
                continue
            added, pos = b._added, b._pos
            delta = added - self._synced_added[e]
            if delta >= size or (self._synced_added[e] == 0 and b.full):
                # first sync, or more rows landed than the ring holds:
                # everything currently stored (window ending at pos)
                start = pos if b.full else 0
                n = size if b.full else pos
                rows.extend((e, (start + i) % size) for i in range(n))
            else:
                if self._synced_added[e] > 0:
                    # re-ship the previous sync's newest row: restart
                    # surgery (mark_restart) may have edited it in place
                    # after it was mirrored; one duplicate row is noise
                    rows.append((e, (pos - delta - 1) % size))
                rows.extend((e, (pos - delta + i) % size) for i in range(delta))
            self._synced_added[e] = added
        rows.extend(self._dirty_rows)
        self._dirty_rows.clear()
        return rows

    def sync(self) -> None:
        """Ship new/edited host rows into the HBM ring (async dispatch)."""
        if all(b.empty for b in self._rb.buffer):
            return
        self._ensure_ring()
        rows = self._pending_rows()
        if not rows:
            return
        with Span("Time/replay_sync", rows=len(rows)) as span:
            size = self._rb.buffer_size
            n = len(rows)
            padded = -(-n // self._bucket) * self._bucket
            t_idx = np.full((padded,), size, dtype=np.int32)  # size ⇒ mode="drop"
            e_idx = np.zeros((padded,), dtype=np.int32)
            t_idx[:n] = [r for _, r in rows]
            e_idx[:n] = [e for e, _ in rows]
            # one fancy-indexed copy per (env, key) — a resume backlog can be the
            # whole buffer, where a per-row python loop would stall startup
            by_env: Dict[int, List[int]] = {}
            for i, (e, _) in enumerate(rows):
                by_env.setdefault(e, []).append(i)
            data: Dict[str, np.ndarray] = {}
            for k in self._ring:
                item = self._rb.buffer[0][k].shape[2:]
                out = np.zeros((padded,) + item, dtype=self._rb.buffer[0][k].dtype)
                for e, slots in by_env.items():
                    out[slots] = self._rb.buffer[e][k][t_idx[slots], 0]
                data[k] = as_stored(out, item)  # a view: the rows cross in the ring's shape
            span.count(bytes=sum(v.nbytes for v in data.values()) + t_idx.nbytes + e_idx.nbytes)
            dev = self._device
            self._ring = _scatter_rows(
                self._ring,
                {k: jax.device_put(v, dev) for k, v in data.items()},
                jax.device_put(t_idx, dev),
                jax.device_put(e_idx, dev),
            )

    # -- sampling ----------------------------------------------------------
    def _sample_indices(self, g: int) -> Tuple[np.ndarray, np.ndarray]:
        """Host-side index draw mirroring EnvIndependentReplayBuffer.sample:
        multinomial split over ready envs, then per-env sequential window
        starts. Returns (t_idx [g, L, B], env_order [B])."""
        rb, L, B = self._rb, self._seq, self._batch
        ready = [
            (e, b) for e, b in enumerate(rb.buffer) if not b.empty and (b.full or b._pos > 0)
        ]
        if not ready:
            raise ValueError("No data in the buffer, cannot sample")
        split = rb._rng.multinomial(B, [1 / len(ready)] * len(ready))
        starts_cols: List[np.ndarray] = []
        env_order: List[int] = []
        for (e, b), bs in zip(ready, split):
            if bs == 0:
                continue
            s = b.sample_starts(int(bs) * g, L).reshape(g, int(bs))
            starts_cols.append(s)
            env_order.extend([e] * int(bs))
        starts = np.concatenate(starts_cols, axis=1)  # [g, B]
        t_idx = (starts[:, None, :] + np.arange(L)[None, :, None]) % rb.buffer_size
        return t_idx.astype(np.int32), np.asarray(env_order, np.int32)

    def _f32_keys(self) -> Tuple[str, ...]:
        proto = self._rb.buffer[0]
        return tuple(
            k for k in proto.keys() if k not in self._cnn_keys and proto[k].dtype != np.float32
        )

    def _gather(self, g: int) -> Any:
        self.sync()
        with Span("Time/replay_sample", grad_steps=g):
            t_idx, env_order = self._sample_indices(g)
            self._last_idx = (t_idx, env_order)
            dev = self._device
            return _gather_batch(
                self._ring,
                jax.device_put(t_idx, dev),
                jax.device_put(env_order, dev),
                self._f32_keys(),
                items=self._items,
            )

    def resync(self) -> None:
        """Forget the mirror and rebuild from host state on next use (after
        a checkpoint load rewired the host buffers)."""
        self._ring = None
        self._synced_added = [0] * self._rb.n_envs
        self._staged = None
        self._dirty_rows.clear()


class _EnvSlice:
    """View of an :class:`EnvIndependentReplayBuffer` restricted to the
    contiguous env block one mesh device mirrors — exposes exactly the
    surface :class:`DeviceRingPrefetcher` consumes, so the per-device
    sub-rings reuse the single-device implementation unchanged. The sample
    rng is the parent buffer's: index draws stay on the one checkpointed
    stream regardless of device count."""

    def __init__(self, rb: EnvIndependentReplayBuffer, lo: int, hi: int):
        self._parent = rb
        self._lo, self._hi = int(lo), int(hi)
        self._rng = rb._rng

    @property
    def buffer(self) -> List[Any]:
        return self._parent.buffer[self._lo : self._hi]

    @property
    def n_envs(self) -> int:
        return self._hi - self._lo

    @property
    def buffer_size(self) -> int:
        return self._parent.buffer_size


class _ShardedRing(_StagedGather):
    """Shared mechanics of the dp-sharded ring variants: per-device shard
    prefetchers built by the subclass, batches assembled pre-sharded with
    :func:`jax.make_array_from_single_device_arrays` along the batch axis
    the subclass names (2 for sequential [G, T, B], 1 for uniform [G, B]).

    Warmup: each shard samples only its own env block, so early in a run one
    device's block can have no ready sub-buffer while others already do —
    the per-shard gather then raises. With a host fallback attached (the
    factories pass the same host sample fn the non-ring path would use) the
    batch is served host-staged until every block has data; without one the
    error surfaces with the warmup context spelled out."""

    _batch_axis: int  # set by subclasses
    _shards: List[Any]
    _batch_sharding: Any
    _fallback: Optional[Any] = None  # host sample fn: g -> host [G, ...] batch
    _warned_warmup: bool = False
    _ring_served = False  # at least one successful sharded gather

    def attach_fallback(self, sample_fn: Any) -> "_ShardedRing":
        self._fallback = sample_fn
        return self

    @property
    def ring(self) -> Optional[List[Dict[str, jax.Array]]]:
        rings = [s.ring for s in self._shards]
        return None if any(r is None for r in rings) else rings

    def sync(self) -> None:
        for s in self._shards:
            s.sync()

    def _gather(self, g: int) -> Any:
        ax = self._batch_axis
        try:
            parts = [s._gather(g) for s in self._shards]
        except ValueError as err:
            # one device block has no ready sub-buffer yet (warmup) — but
            # once the ring has served a batch, a gather ValueError is a
            # real bug, not a warmup hole: never silently downgrade the run
            if self._ring_served or self._fallback is None:
                raise ValueError(
                    "sharded device ring gather failed"
                    + (
                        " AFTER the ring had already served (not a warmup hole)"
                        if self._ring_served
                        else ": a device's env block has no ready sub-buffer yet "
                        "(warmup) and no host fallback is attached"
                    )
                    + f"; underlying error: {err}"
                ) from err
            if not self._warned_warmup:
                self._warned_warmup = True
                import sys

                print(
                    "[device_ring] warmup: not every device block has replay data "
                    "yet; serving host-staged batches until the sharded ring is "
                    f"ready (shard gather: {err})",
                    file=sys.stderr,
                )
            return jax.tree.map(
                lambda x: jax.device_put(x, self._batch_sharding), self._fallback(g)
            )
        self._ring_served = True
        out: Dict[str, jax.Array] = {}
        for k in parts[0]:
            shards = [p[k] for p in parts]
            lead = shards[0].shape
            shape = lead[:ax] + (sum(s.shape[ax] for s in shards),) + lead[ax + 1 :]
            out[k] = jax.make_array_from_single_device_arrays(
                shape, self._batch_sharding, shards
            )
        return out

    def resync(self) -> None:
        for s in self._shards:
            s.resync()
        self._staged = None


class ShardedDeviceRingPrefetcher(_ShardedRing):
    """dp-sharded HBM replay ring for multi-device meshes (VERDICT r4 #3).

    Device ``d`` of the ``dp`` axis mirrors env block ``d`` and gathers its
    own ``batch/D`` columns with the single-device ring machinery; the
    global ``[G, T, B, ...]`` training batch is assembled from the
    per-device pieces with :func:`jax.make_array_from_single_device_arrays`
    — already laid out exactly as ``P(None, None, "dp")``. Rows still cross
    from host to device once each, and NO collective ever touches the ring:
    scatters and gathers are purely device-local.

    Sampling semantics vs the host path: each device's columns draw only
    from its own env block (an even per-device allocation instead of one
    global cross-env multinomial). With the reference's uniform multinomial
    this is the same marginal distribution whenever n_envs % D == 0, which
    the constructor requires."""

    def __init__(
        self,
        rb: EnvIndependentReplayBuffer,
        batch_size: int,
        sequence_length: int,
        cnn_keys: Sequence[str] = (),
        dist: Any = None,
        bucket: int = 8,
        emit: Optional[Any] = None,
    ):
        devs = list(dist.mesh.devices.flatten())
        D = len(devs)
        if rb.n_envs % D or batch_size % D:
            raise ValueError(
                f"sharded device ring needs n_envs ({rb.n_envs}) and batch_size "
                f"({batch_size}) divisible by the mesh size ({D})"
            )
        epd, bpd = rb.n_envs // D, batch_size // D
        self._epd = epd
        self._shards = [
            DeviceRingPrefetcher(
                _EnvSlice(rb, d * epd, (d + 1) * epd),
                bpd,
                sequence_length,
                cnn_keys=cnn_keys,
                device=devs[d],
                bucket=bucket,
                emit=emit,
            )
            for d in range(D)
        ]
        self._batch_sharding = dist.shard_batch_axis(2)  # [G, T, B, ...]
        self._batch_axis = 2
        self._staged: Optional[tuple] = None

    def mark_dirty(self, env_idx: int, row: int) -> None:
        self._shards[env_idx // self._epd].mark_dirty(env_idx % self._epd, row)


@functools.partial(jax.jit, donate_argnums=0)
def _scatter_steps(ring: Dict[str, jax.Array], rows: Dict[str, jax.Array],
                   t_idx: jax.Array) -> Dict[str, jax.Array]:
    # one scatter row covers all envs of a time step, written in the ring's
    # own item shape; padding is OOB-dropped
    return {
        k: ring[k].at[t_idx].set(rows[k].reshape(rows[k].shape[:1] + ring[k].shape[1:]), mode="drop")
        for k in ring
    }


@functools.partial(jax.jit, static_argnames=("g", "batch", "next_keys", "f32_keys", "items"))
def _gather_uniform(ring: Dict[str, jax.Array], t_idx: jax.Array, e_idx: jax.Array,
                    g: int, batch: int, next_keys: Tuple[str, ...],
                    f32_keys: Tuple[str, ...], items: _Items = ()) -> Dict[str, jax.Array]:
    size = next(iter(ring.values())).shape[0]
    logical = dict(items)
    shape = {k: (g, batch) + logical.get(k, ring[k].shape[2:]) for k in ring}
    out = {k: ring[k][t_idx, e_idx].reshape(shape[k]) for k in ring}
    nxt = (t_idx + 1) % size
    for k in next_keys:
        out[f"next_{k}"] = ring[k][nxt, e_idx].reshape(shape[k])
    def _f32(k: str) -> bool:
        return k in f32_keys or (k.startswith("next_") and k[5:] in f32_keys)

    return {k: v.astype(jnp.float32) if _f32(k) else v for k, v in out.items()}


class DeviceUniformRingPrefetcher(_StagedGather):
    """HBM mirror of a plain :class:`ReplayBuffer` serving uniform
    ``[G, B, ...]`` batches (the SAC / SAC-AE / DroQ template). Same
    ship-each-row-once contract as :class:`DeviceRingPrefetcher`; rows are
    shipped per time step (all envs at once — the buffer adds in lockstep)."""

    def __init__(
        self,
        rb: Any,
        batch_size: int,
        cnn_keys: Sequence[str] = (),
        sample_next_obs: bool = False,
        device: Optional[Any] = None,
        bucket: int = 8,
        emit: Optional[Any] = None,
    ):
        self._rb = rb
        self._batch = int(batch_size)
        self._cnn_keys = tuple(cnn_keys)
        self._next_obs = bool(sample_next_obs)
        self._device = device if device is not None else jax.local_devices()[0]
        self._bucket = int(bucket)
        self._emit = emit  # telem.emit: takes the `ring_layout` event at allocation
        self._ring: Optional[Dict[str, jax.Array]] = None
        self._items: _Items = ()  # the leaves stored in another shape than their own
        self._synced_added = 0
        self._staged: Optional[tuple] = None
        self._last_idx: Optional[tuple] = None  # (t_idx, e_idx) — tests

    @property
    def ring(self) -> Optional[Dict[str, jax.Array]]:
        return self._ring

    def _ensure_ring(self) -> None:
        if self._ring is not None:
            return
        b = self._rb
        if b.empty:
            raise ValueError("No data in the buffer, cannot mirror")
        self._ring, self._items = _allocate(b, b.buffer_size, b.n_envs, self._device, self._emit)

    def sync(self) -> None:
        b = self._rb
        if b.empty:
            return
        self._ensure_ring()
        size = b.buffer_size
        delta = b._added - self._synced_added
        if delta <= 0:
            return
        if delta >= size:
            steps = [(b._pos + i) % size for i in range(size)] if b.full else list(range(b._pos))
        else:
            steps = [(b._pos - delta + i) % size for i in range(delta)]
        self._synced_added = b._added
        with Span("Time/replay_sync", rows=len(steps) * b.n_envs) as span:
            n = len(steps)
            padded = -(-n // self._bucket) * self._bucket
            t_idx = np.full((padded,), size, dtype=np.int32)
            t_idx[:n] = steps
            dev = self._device
            data = {}
            nbytes = t_idx.nbytes
            for k in self._ring:
                host = b[k]
                out = np.zeros((padded,) + host.shape[1:], dtype=host.dtype)
                out[:n] = host[steps]
                nbytes += out.nbytes
                data[k] = jax.device_put(as_stored(out, host.shape[2:]), dev)
            span.count(bytes=nbytes)
            self._ring = _scatter_steps(self._ring, data, jax.device_put(t_idx, dev))

    def _f32_keys(self) -> Tuple[str, ...]:
        b = self._rb
        return tuple(k for k in b.keys() if k not in self._cnn_keys and b[k].dtype != np.float32)

    def _gather(self, g: int) -> Any:
        self.sync()
        with Span("Time/replay_sample", grad_steps=g):
            idxs, env_idxs = self._rb.sample_indices(self._batch * g, self._next_obs)
            self._last_idx = (idxs, env_idxs)
            next_keys = tuple(k for k in self._rb._obs_keys if k in self._rb.keys()) if self._next_obs else ()
            dev = self._device
            return _gather_uniform(
                self._ring,
                jax.device_put(idxs.astype(np.int32), dev),
                jax.device_put(env_idxs.astype(np.int32), dev),
                g,
                self._batch,
                next_keys,
                self._f32_keys(),
                items=self._items,
            )

    def resync(self) -> None:
        self._ring = None
        self._synced_added = 0
        self._staged = None


class _UniformEnvSlice:
    """View of a plain :class:`ReplayBuffer` restricted to a contiguous env
    block — the uniform-ring counterpart of :class:`_EnvSlice`. Row-validity
    state (`_pos`/`_added`/`full`) is shared with the parent (the buffer
    adds in lockstep across envs); env draws are re-sampled locally from the
    parent's checkpointed rng so each device's columns come from its own
    block."""

    def __init__(self, rb: Any, lo: int, hi: int):
        self._parent = rb
        self._lo, self._hi = int(lo), int(hi)
        self._rng = rb._rng
        self._obs_keys = rb._obs_keys

    @property
    def buffer_size(self) -> int:
        return self._parent.buffer_size

    @property
    def n_envs(self) -> int:
        return self._hi - self._lo

    @property
    def empty(self) -> bool:
        return self._parent.empty

    @property
    def full(self) -> bool:
        return self._parent.full

    @property
    def _pos(self) -> int:
        return self._parent._pos

    @property
    def _added(self) -> int:
        return self._parent._added

    def keys(self):
        return self._parent.keys()

    def __getitem__(self, key: str) -> np.ndarray:
        return np.asarray(self._parent[key])[:, self._lo : self._hi]

    def sample_indices(self, total: int, sample_next_obs: bool = False):
        # parent row validity + a local env draw (uniform over this block ==
        # the global uniform conditioned on the block, since adds are lockstep)
        idxs, _ = self._parent.sample_indices(total, sample_next_obs)
        return idxs, self._rng.integers(0, self.n_envs, size=total)


class ShardedDeviceUniformRingPrefetcher(_ShardedRing):
    """dp-sharded uniform ([G, B, ...]) HBM ring — the SAC-family twin of
    :class:`ShardedDeviceRingPrefetcher`: device *d* mirrors env block *d*
    via :class:`_UniformEnvSlice` + a per-device
    :class:`DeviceUniformRingPrefetcher`; the global batch is assembled
    pre-sharded as ``P(None, "dp")`` with no collectives."""

    def __init__(
        self,
        rb: Any,
        batch_size: int,
        cnn_keys: Sequence[str] = (),
        sample_next_obs: bool = False,
        dist: Any = None,
        bucket: int = 8,
        emit: Optional[Any] = None,
    ):
        devs = list(dist.mesh.devices.flatten())
        D = len(devs)
        if rb.n_envs % D or batch_size % D:
            raise ValueError(
                f"sharded uniform ring needs n_envs ({rb.n_envs}) and batch_size "
                f"({batch_size}) divisible by the mesh size ({D})"
            )
        epd = rb.n_envs // D
        self._shards = [
            DeviceUniformRingPrefetcher(
                _UniformEnvSlice(rb, d * epd, (d + 1) * epd),
                batch_size // D,
                cnn_keys=cnn_keys,
                sample_next_obs=sample_next_obs,
                device=devs[d],
                bucket=bucket,
                emit=emit,
            )
            for d in range(D)
        ]
        self._batch_sharding = dist.shard_batch_axis(1)  # [G, B, ...]
        self._batch_axis = 1
        self._staged: Optional[tuple] = None


def _ring_mode(cfg: Any) -> str:
    """Parse buffer.device_cache: YAML booleans arrive as real bools, so
    `device_cache: false` must force the ring OFF, not fall through an
    `or "auto"` truthiness hole."""
    raw = cfg.select("buffer.device_cache", "auto")
    mode = "auto" if raw is None else str(raw).lower()
    if mode not in ("auto", "true", "false"):
        raise ValueError(f"buffer.device_cache must be auto|true|false, got '{raw}'")
    return mode


def _use_ring(
    cfg: Any,
    dist: Any,
    row_bytes_hint: Optional[int],
    rb_rows: int,
    multi_ok: bool = False,
) -> bool:
    mode = _ring_mode(cfg)
    if mode == "false":
        return False
    if dist.world_size > 1 and not multi_ok:
        if mode == "true":
            raise ValueError(
                "buffer.device_cache=true is single-device on this replay "
                f"path (got {dist.world_size} devices); use auto or false"
            )
        return False
    if mode == "true":
        return True
    cap = int(cfg.select("buffer.device_cache_max_bytes", 6_000_000_000) or 0)
    return (
        # the MESH devices decide, not whatever backend the host also has:
        # a cpu-forced run on an accelerator machine must not build a ring.
        # Multi-device (multi_ok): the ring shards over dp, so the per-device
        # HBM cost is total/world_size.
        all(getattr(d, "platform", "cpu") != "cpu" for d in dist.devices)
        and (row_bytes_hint or 0) * rb_rows <= cap * dist.world_size
    )


def estimate_row_bytes(obs_space: Any, act_dim: int) -> int:
    """Bytes one (time, env) replay row occupies mirrored in HBM: dict-obs
    leaves at their stored dtype (images stay uint8) + one-hot/continuous
    action + the four f32 scalars (reward/terminated/truncated/is_first)."""
    total = 0
    for space in obs_space.spaces.values():
        total += int(np.prod(space.shape)) * np.dtype(space.dtype).itemsize
    return total + 4 * int(act_dim) + 4 * 4


def _sharded_or_fallback(cfg: Any, dist: Any, rb: Any, batch_size: int, make_sharded):
    """The multi-device ring-vs-fallback policy shared by both replay paths:
    build the dp-sharded ring when the mesh is process-local and n_envs /
    the global batch divide it; otherwise raise under forced
    ``device_cache=true`` or fall back to host staging with a stderr note.
    Returns the sharded prefetcher or None (= caller uses the host path)."""
    local = set(jax.local_devices())
    if any(d not in local for d in dist.mesh.devices.flat):
        # multi-host mesh: this process cannot device_put to other
        # processes' chips — replay stays host-staged (each process feeds
        # its own shard of the dp batch)
        msg = (
            "sharded device ring requires all mesh devices to be "
            "process-local (multi-host meshes stay host-staged)"
        )
    elif not getattr(dist, "is_pure_dp", True):
        # multi-axis mesh (fsdp/tp): the ring's one-env-block-per-device
        # layout IS the pure-dp batch placement; fsdp/tp batches need the
        # engine's (dp, fsdp)-sharded staging instead
        msg = (
            f"sharded device ring is pure-dp only (mesh is dp={dist.dp} "
            f"fsdp={dist.fsdp} tp={dist.tp}); multi-axis meshes stay host-staged"
        )
    elif rb.n_envs % dist.world_size == 0 and batch_size % dist.world_size == 0:
        return make_sharded()
    else:
        msg = (
            f"sharded device ring needs env.num_envs ({rb.n_envs}) and the "
            f"global batch size ({batch_size}) divisible by the mesh size "
            f"({dist.world_size})"
        )
    if _ring_mode(cfg) == "true":  # explicitly forced: fail loudly
        raise ValueError(msg)
    import sys

    print(f"[device_ring] {msg}; falling back to host-staged batches", file=sys.stderr)
    return None


def make_sequential_prefetcher(
    cfg: Any,
    dist: Any,
    rb: EnvIndependentReplayBuffer,
    batch_size: int,
    sequence_length: int,
    cnn_keys: Sequence[str] = (),
    host_sample_fn: Optional[Any] = None,
    row_bytes_hint: Optional[int] = None,
    emit: Optional[Any] = None,
):
    """Prefetcher for the sequential-replay (Dreamer-family) train loops.

    ``buffer.device_cache`` ∈ {auto, true, false}: ``true`` forces the HBM
    ring (tests use this on CPU), ``false`` forces the host path,
    ``auto`` enables the ring on non-CPU meshes when the mirrored buffer
    fits ``buffer.device_cache_max_bytes`` per device. Multi-device meshes
    get the dp-sharded ring (:class:`ShardedDeviceRingPrefetcher`) when
    n_envs and batch_size divide the mesh; otherwise the host path runs
    (with a stderr note — no silent layout surprises). ``emit``
    (``telem.emit``) takes a ring's ``ring_layout`` event when it allocates."""
    supported = isinstance(rb, EnvIndependentReplayBuffer) and all(
        isinstance(b, SequentialReplayBuffer) for b in rb.buffer
    )
    if host_sample_fn is None:
        def host_sample_fn(g):  # noqa: F811 — default sequential host sample
            s = rb.sample(batch_size, sequence_length=sequence_length, n_samples=g)
            return {
                k: np.asarray(v) if k in cnn_keys else np.asarray(v, np.float32)
                for k, v in s.items()
            }
    if supported and _use_ring(
        cfg, dist, row_bytes_hint, rb.buffer_size * rb.n_envs, multi_ok=True
    ):
        if dist.world_size == 1:
            return DeviceRingPrefetcher(
                rb, batch_size, sequence_length, cnn_keys=cnn_keys, device=dist.local_device, emit=emit
            )
        sharded = _sharded_or_fallback(
            cfg, dist, rb, batch_size,
            lambda: ShardedDeviceRingPrefetcher(
                rb, batch_size, sequence_length, cnn_keys=cnn_keys, dist=dist, emit=emit
            ),
        )
        if sharded is not None:
            # warmup hole: a device block with no ready sub-buffer serves
            # host-staged batches instead of raising (satellite ADVICE r5)
            return sharded.attach_fallback(host_sample_fn)
    return StagedPrefetcher(host_sample_fn, dist.shard_batch_axis(2))


def make_uniform_prefetcher(
    cfg: Any,
    dist: Any,
    rb: Any,
    batch_size: int,
    cnn_keys: Sequence[str] = (),
    sample_next_obs: bool = False,
    host_sample_fn: Optional[Any] = None,
    row_bytes_hint: Optional[int] = None,
    emit: Optional[Any] = None,
):
    """Prefetcher for the uniform-replay (SAC-family) train loops: the HBM
    ring under the same ``buffer.device_cache`` policy as the sequential
    path (incl. the dp-sharded variant on multi-device meshes), else host
    sampling staged one burst ahead ([G, B, ...] batches). ``emit`` as in
    :func:`make_sequential_prefetcher`."""
    if host_sample_fn is None:
        def host_sample_fn(g):  # noqa: F811 — default uniform host sample
            s = rb.sample(batch_size * g, sample_next_obs=sample_next_obs, n_samples=1)
            return {
                k: np.asarray(v).reshape(g, batch_size, *np.asarray(v).shape[2:])
                for k, v in s.items()
            }
    if _use_ring(cfg, dist, row_bytes_hint, rb.buffer_size * rb.n_envs, multi_ok=True):
        if dist.world_size == 1:
            return DeviceUniformRingPrefetcher(
                rb,
                batch_size,
                cnn_keys=cnn_keys,
                sample_next_obs=sample_next_obs,
                device=dist.local_device,
                emit=emit,
            )
        sharded = _sharded_or_fallback(
            cfg, dist, rb, batch_size,
            lambda: ShardedDeviceUniformRingPrefetcher(
                rb,
                batch_size,
                cnn_keys=cnn_keys,
                sample_next_obs=sample_next_obs,
                dist=dist,
                emit=emit,
            ),
        )
        if sharded is not None:
            # warmup hole: a device block with no ready sub-buffer serves
            # host-staged batches instead of raising (satellite ADVICE r5)
            return sharded.attach_fallback(host_sample_fn)
    return StagedPrefetcher(host_sample_fn, dist.shard_batch_axis(1))
