"""`InferencePolicy` — one checkpoint→policy adapter for every algorithm.

The uniform serving/evaluation contract: a registered *policy builder* wraps
an algo's agent modules behind a single batched ``apply`` with the canonical
signature

    apply(params, obs, state, key, greedy) -> (actions, new_state, new_key)

(`state` is ``None`` for feed-forward policies; recurrent ones — DreamerV3 —
carry their latent state through it). `InferencePolicy` owns:

* **bucketed compilation** — the apply fn is jitted once per power-of-two
  batch bucket (and per greedy variant); requests are zero-padded up to the
  bucket so concurrent traffic with mixed batch sizes never triggers an XLA
  retrace after `warmup()`. Traces are counted through the process
  `RetraceDetector`, so the serve telemetry can prove the steady state
  compiles nothing.
* **double-buffered params** — `swap_params(new_state_params)` stages the new
  weights on the inference device and swaps a single reference under a lock;
  batches already dispatched keep the old buffers (JAX arrays are immutable),
  so hot-reload never corrupts an in-flight request.
* **per-session recurrent state** — a `SessionStore` maps session ids to
  host-side state rows; `act()` gathers the rows of a batch, steps them
  together, and scatters the updated rows back.

Builders are registered per algo name in `serve.builders`; evaluation
(`serve.evaluate`) and the serving stack (`serve.batcher` / `serve.server`)
both go through this class, so there is exactly one checkpoint→policy path.
"""
from __future__ import annotations

import pathlib
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..telemetry.xla import RETRACE_DETECTOR

DEFAULT_BUCKETS = (1, 2, 4, 8, 16)

# algo name -> builder(cfg, observation_space, action_space) -> PolicyCore
POLICY_BUILDERS: Dict[str, Callable] = {}


def register_policy_builder(*names: str) -> Callable:
    """Register a policy builder for one or more algorithm names."""

    def wrap(fn: Callable) -> Callable:
        for name in names:
            if name in POLICY_BUILDERS:
                raise ValueError(f"Policy builder for '{name}' already registered")
            POLICY_BUILDERS[name] = fn
        return fn

    return wrap


def get_policy_builder(name: str) -> Callable:
    from . import builders  # noqa: F401  (populates POLICY_BUILDERS on import)

    if name not in POLICY_BUILDERS:
        raise ValueError(
            f"No policy builder registered for '{name}'. Available: {sorted(POLICY_BUILDERS)}"
        )
    return POLICY_BUILDERS[name]


@dataclass
class PolicyCore:
    """What a builder hands back: the pure functions of one algo's policy.

    ``apply`` must be jit-compatible with ``greedy`` static; ``extract_params``
    maps a checkpoint's full ``state['params']`` tree to the (smaller)
    inference subtree — the optimizer/critic/target leaves never reach the
    serving device.
    """

    apply: Callable  # (params, obs, state, key, greedy) -> (actions, state, key)
    extract_params: Callable[[Any], Any]
    prepare: Callable[[Dict[str, np.ndarray], int], Any]  # raw env obs -> batched tree
    dummy_obs: Callable[[int], Any]  # batch size -> zeros obs tree (for warmup)
    init_state: Optional[Callable] = None  # (params, n) -> state tree; None = stateless
    name: str = "policy"

    @property
    def stateful(self) -> bool:
        return self.init_state is not None


class SessionExpired(KeyError):
    """The session's recurrent state was LRU-evicted while the session was
    still live. Re-initializing the latent silently would corrupt the
    session's trajectory — the server answers HTTP 410 instead, and the
    gateway re-hydrates from its broker copy."""

    def __init__(self, sid: str) -> None:
        super().__init__(f"session '{sid}' expired: its state was evicted (LRU bound)")
        self.session_id = str(sid)


class SessionStore:
    """Host-side per-session recurrent state rows (each a [1, ...] tree).

    Bounded: beyond ``max_sessions`` ids the least-recently-used row is
    evicted, so a long-running server with per-user ids cannot leak host
    memory. Evicted ids leave a TOMBSTONE (itself bounded): a later request
    for a tombstoned session is distinguishable from a brand-new session —
    the act path raises :class:`SessionExpired` (HTTP 410) instead of
    silently restarting the latent from the initial state. Re-hydrating the
    session (``put``) clears its tombstone. ``on_evict(sid)`` fires per
    eviction so the serving stats can count them."""

    def __init__(self, max_sessions: int = 4096, max_tombstones: Optional[int] = None) -> None:
        from collections import OrderedDict

        self.max_sessions = int(max_sessions)
        self.max_tombstones = int(max_tombstones if max_tombstones is not None else 4 * self.max_sessions)
        self.on_evict: Optional[Any] = None  # callback(sid), set by the serving layer
        self._rows: "OrderedDict[str, Any]" = OrderedDict()
        self._tombstones: "OrderedDict[str, bool]" = OrderedDict()
        self._lock = threading.Lock()

    def get(self, sid: str) -> Optional[Any]:
        with self._lock:
            row = self._rows.get(sid)
            if row is not None:
                self._rows.move_to_end(sid)
            return row

    def put(self, sid: str, row: Any) -> None:
        evicted: List[str] = []
        with self._lock:
            self._rows[sid] = row
            self._rows.move_to_end(sid)
            self._tombstones.pop(sid, None)  # (re)hydrated: no longer expired
            while len(self._rows) > self.max_sessions:
                old_sid, _ = self._rows.popitem(last=False)
                self._tombstones[old_sid] = True
                self._tombstones.move_to_end(old_sid)
                evicted.append(old_sid)
            while len(self._tombstones) > self.max_tombstones:
                self._tombstones.popitem(last=False)
        # callbacks run outside the lock: an emitting sink must not block puts
        cb = self.on_evict
        if cb is not None:
            for old_sid in evicted:
                try:
                    cb(old_sid)
                except Exception:
                    pass

    def expired(self, sid: str) -> bool:
        """True when this id's state was evicted and never re-hydrated."""
        with self._lock:
            return sid in self._tombstones

    def drop(self, sid: str) -> None:
        with self._lock:
            self._rows.pop(sid, None)
            self._tombstones.pop(sid, None)

    def clear(self) -> None:
        with self._lock:
            self._rows.clear()
            self._tombstones.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._rows)


def env_action(row: np.ndarray, action_space: Any) -> Any:
    """Convert one action row of a batch to what `env.step` expects."""
    import gymnasium as gym

    row = np.asarray(row)
    if isinstance(action_space, gym.spaces.Box):
        return row.reshape(action_space.shape)
    if isinstance(action_space, gym.spaces.MultiDiscrete):
        return row.reshape(-1)
    return row.reshape(-1)[0].item()


def _bucket_for(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if b >= n:
            return b
    return buckets[-1]


_POLICY_SEQ = threading.Lock(), [0]


def _next_tag(name: str) -> str:
    lock, counter = _POLICY_SEQ
    with lock:
        counter[0] += 1
        return f"serve.apply[{name}]#{counter[0]}"


class InferencePolicy:
    """A trained checkpoint behind one batched ``act`` API."""

    def __init__(
        self,
        core: PolicyCore,
        state_params: Any,
        cfg: Any = None,
        buckets: Optional[Sequence[int]] = None,
    ) -> None:
        import jax

        from ..parallel.placement import player_device

        self.core = core
        self.cfg = cfg
        raw = list(buckets if buckets is not None else (cfg.select("serve.buckets") if cfg is not None else None) or DEFAULT_BUCKETS)
        self.buckets: List[int] = sorted({int(b) for b in raw})
        if any(b <= 0 for b in self.buckets):
            raise ValueError(f"serve.buckets must be positive, got {self.buckets}")
        self.device = player_device(cfg)
        self._params_lock = threading.Lock()
        self._act_lock = threading.Lock()
        self._params = jax.device_put(core.extract_params(state_params), self.device)
        # serve.seed may exist as an explicit null — fall back to the run's
        # seed in that case too, not only when the key is absent
        serve_seed = cfg.select("serve.seed") if cfg is not None else None
        if serve_seed is None:
            serve_seed = (cfg.select("seed", 0) if cfg is not None else 0) or 0
        self._key = jax.device_put(jax.random.key(int(serve_seed)), self.device)
        self.sessions = SessionStore(
            int(cfg.select("serve.max_sessions", 4096) or 4096) if cfg is not None else 4096
        )
        self.reload_count = 0
        self.params_version = 0
        import time as _time

        # monotonic stamp of the last param (re)load: /healthz reports the
        # age so the gateway's routing can prefer fresh replicas
        self.params_refreshed_at = _time.monotonic()
        self._init_row: Optional[Any] = None
        self._tag = _next_tag(core.name)
        # `greedy` is baked in as a closure constant (two executables per
        # bucket) instead of a static argnum — both trace through the same
        # detector tag, so retrace accounting covers either variant
        traced = RETRACE_DETECTOR.wrap(core.apply, self._tag)
        self._jit_variants = {
            True: jax.jit(lambda p, o, s, k: traced(p, o, s, k, True)),
            False: jax.jit(lambda p, o, s, k: traced(p, o, s, k, False)),
        }
        self._traces_at_warmup = 0
        # canonical per-leaf obs spec (from the builder's dummy obs): what a
        # prepared request must look like, checked before it can join a batch
        template = core.dummy_obs(1)
        flat, self._obs_treedef = jax.tree_util.tree_flatten_with_path(template)
        self._obs_spec = [
            (jax.tree_util.keystr(p), tuple(np.asarray(l).shape[1:]), np.asarray(l).dtype)
            for p, l in flat
        ]
        if core.stateful:
            self._refresh_init_row()

    # -- construction ------------------------------------------------------
    @classmethod
    def from_state(
        cls,
        cfg: Any,
        state_params: Any,
        observation_space: Any,
        action_space: Any,
        buckets: Optional[Sequence[int]] = None,
    ) -> "InferencePolicy":
        algo = str(cfg.select("algo.name"))
        core = get_policy_builder(algo)(cfg, observation_space, action_space)
        return cls(core, state_params, cfg=cfg, buckets=buckets)

    @classmethod
    def from_checkpoint(
        cls,
        ckpt_path: Any,
        cfg: Any = None,
        buckets: Optional[Sequence[int]] = None,
    ) -> "InferencePolicy":
        """Build from a checkpoint file; the run's saved ``config.yaml`` is
        loaded from beside it when ``cfg`` is not given. The load skips
        optimizer state and replay buffers (`load_for_inference`)."""
        from ..config import Config, load_config_file
        from ..utils.checkpoint import CheckpointManager
        from ..utils.env import vectorize

        ckpt_path = pathlib.Path(ckpt_path)
        if cfg is None:
            cfg_path = ckpt_path.parent.parent / "config.yaml"
            if not cfg_path.is_file():
                raise FileNotFoundError(f"Missing saved config beside checkpoint: {cfg_path}")
            cfg = load_config_file(cfg_path)
        state = CheckpointManager.load_for_inference(ckpt_path)
        spec_cfg = Config(cfg.to_dict())
        spec_cfg.set_path("env.num_envs", 1)
        spec_cfg.set_path("env.capture_video", False)
        spec_cfg.set_path("env.sync_env", True)
        envs = vectorize(spec_cfg, int(cfg.select("seed", 0) or 0), 0)
        try:
            obs_space = envs.single_observation_space
            act_space = envs.single_action_space
        finally:
            envs.close()
        return cls.from_state(cfg, state["params"], obs_space, act_space, buckets=buckets)

    # -- hot reload --------------------------------------------------------
    def swap_params(self, state_params: Any) -> int:
        """Double-buffered weight swap: stage the new inference subtree on the
        serving device, then swap one reference. In-flight batches keep the
        old (immutable) buffers; the next batch picks up the new ones."""
        import jax

        new = jax.device_put(self.core.extract_params(state_params), self.device)
        # force materialization before publishing, so no batch ever blocks on
        # a half-transferred tree
        for leaf in jax.tree.leaves(new):
            getattr(leaf, "block_until_ready", lambda: None)()
        import time as _time

        with self._params_lock:
            self._params = new
            self.params_version += 1
            self.reload_count += 1
            self.params_refreshed_at = _time.monotonic()
            version = self.params_version
        if self.core.stateful:
            self._refresh_init_row()
        return version

    def params_staleness_s(self) -> float:
        """Seconds since the served params were last loaded or swapped."""
        import time as _time

        with self._params_lock:
            return max(0.0, _time.monotonic() - self.params_refreshed_at)

    def current_params(self) -> Tuple[Any, int]:
        with self._params_lock:
            return self._params, self.params_version

    def _refresh_init_row(self) -> None:
        import jax

        params, _ = self.current_params()
        row = self.core.init_state(params, 1)  # type: ignore[misc]
        self._init_row = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), row)

    # -- warmup / retrace accounting ---------------------------------------
    def warmup(self, greedy_variants: Sequence[bool] = (True, False)) -> int:
        """Compile the apply fn for every (bucket, greedy) combination; after
        this, any batch up to the largest bucket hits a cached executable.
        Returns the number of traces performed."""
        import jax

        before = RETRACE_DETECTOR.trace_count(self._tag)
        params, _ = self.current_params()
        for b in self.buckets:
            obs = self.core.dummy_obs(b)
            state = None
            if self.core.stateful:
                state = self._stack_rows([self._init_row] * b)
            for greedy in greedy_variants:
                out = self._jit_variants[bool(greedy)](params, obs, state, self._key)
                jax.block_until_ready(out)
        self._traces_at_warmup = RETRACE_DETECTOR.trace_count(self._tag)
        return self._traces_at_warmup - before

    def retraces_since_warmup(self) -> int:
        return max(0, RETRACE_DETECTOR.trace_count(self._tag) - self._traces_at_warmup)

    def roofline_records(self) -> list:
        """One roofline verdict per compiled bucket (greedy variant): XLA
        cost analysis of the bucketed apply vs this device's roof. Serving
        is almost always memory-bound at bucket size 1 and climbs toward the
        ridge as occupancy grows — this quantifies exactly how much roof a
        fuller bucket buys. Best-effort: returns [] on backends without cost
        analysis."""
        from ..telemetry.throughput import (
            cost_of_lowered,
            peak_bytes_per_s_record,
            peak_flops_record,
            roofline_record,
        )

        out: list = []
        try:
            device = self.device  # the roof of the device the policy runs on
            params, _ = self.current_params()
            flops_rec = peak_flops_record(device)
            bw_rec = peak_bytes_per_s_record(device)
            for b in self.buckets:
                obs = self.core.dummy_obs(b)
                state = None
                if self.core.stateful:
                    state = self._stack_rows([self._init_row] * b)
                lowered = self._jit_variants[True].lower(params, obs, state, self._key)
                rec = roofline_record(
                    f"{self.core.name}_apply_b{b}",
                    cost_of_lowered(lowered),
                    peak_flops=flops_rec.get("peak_flops"),
                    peak_bytes_per_s=bw_rec.get("peak_bytes_per_s"),
                    device_kind=str(getattr(device, "device_kind", "") or ""),
                    basis=str(bw_rec.get("peak_bytes_per_s_basis") or ""),
                    role="replica",
                )
                if rec is not None:
                    out.append(rec)
        except Exception:
            return out
        return out

    # -- the act path ------------------------------------------------------
    def prepare(self, raw_obs: Dict[str, Any], n: int = 1) -> Any:
        return self.core.prepare(raw_obs, n)

    def validate_prepared(self, tree: Any, n: int) -> None:
        """Reject a prepared obs whose structure/shape/dtype deviates from
        the warmed template — BEFORE it can poison a coalesced batch or force
        an unwarmed compile. Raises ValueError with the offending leaf."""
        import jax

        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        if treedef != self._obs_treedef or len(flat) != len(self._obs_spec):
            expected = [p for p, _, _ in self._obs_spec]
            raise ValueError(f"obs structure mismatch: expected leaves {expected}")
        for (path, leaf), (spath, sshape, sdtype) in zip(flat, self._obs_spec):
            a = np.asarray(leaf)
            if a.shape != (n, *sshape):
                raise ValueError(
                    f"obs leaf {spath or 'obs'} has shape {a.shape}, expected {(n, *sshape)}"
                )
            if a.dtype != sdtype:
                raise ValueError(
                    f"obs leaf {spath or 'obs'} has dtype {a.dtype}, expected {sdtype}"
                )

    # -- session externalization (gateway broker protocol) ------------------
    def export_session(self, sid: str) -> Optional[Any]:
        """The session's current host-side state row (None when unknown/
        stateless) — what the replica hands back so the gateway's broker
        stays the source of truth."""
        if not self.core.stateful:
            return None
        return self.sessions.get(sid)

    def import_session(self, sid: str, row: Any) -> None:
        """Install an externalized state row (broker re-hydrate / session
        migration). Overwrites any cached row — the broker's copy wins —
        and clears the session's eviction tombstone."""
        if not self.core.stateful:
            return
        self.sessions.put(sid, row)

    def session_expired(self, sid: str) -> bool:
        return self.core.stateful and self.sessions.expired(sid)

    @staticmethod
    def _stack_rows(rows: List[Any]) -> Any:
        import jax

        return jax.tree.map(lambda *xs: np.concatenate([np.asarray(x) for x in xs], axis=0), *rows)

    @staticmethod
    def _pad(tree: Any, n: int, bucket: int) -> Any:
        if bucket == n:
            return tree
        import jax

        def pad_leaf(x: Any) -> np.ndarray:
            x = np.asarray(x)
            pad = np.zeros((bucket - n,) + x.shape[1:], x.dtype)
            return np.concatenate([x, pad], axis=0)

        return jax.tree.map(pad_leaf, tree)

    def act_batch(
        self,
        obs: Any,
        n: int,
        deterministic: bool = False,
        sessions: Optional[Sequence[Optional[str]]] = None,
        expired_out: Optional[List[int]] = None,
    ) -> np.ndarray:
        """Run one prepared obs batch (leading dim ``n``) through the policy.

        Pads to the enclosing bucket, steps, and slices back to ``n`` rows.
        Batches larger than the largest bucket are processed in max-bucket
        chunks. For stateful policies, per-session state rows are gathered
        before and scattered after the step (``sessions[i] is None`` rows act
        from a fresh initial state and are not persisted).

        ``expired_out`` (when given) collects the indices of sessions whose
        state was LRU-evicted AFTER the caller's expiry check but BEFORE this
        gather — the submit→gather race. Those rows run on a throwaway
        initial state and are neither persisted nor safe to ack: the caller
        must fail each one with :class:`SessionExpired` so the client
        re-hydrates, instead of silently restarting the latent (and then
        poisoning whatever trusts the returned state).
        """
        import jax

        max_bucket = self.buckets[-1]
        if n > max_bucket:
            outs = []
            for lo in range(0, n, max_bucket):
                hi = min(n, lo + max_bucket)
                chunk = jax.tree.map(lambda x: np.asarray(x)[lo:hi], obs)
                sess = sessions[lo:hi] if sessions is not None else None
                sub_expired: Optional[List[int]] = [] if expired_out is not None else None
                outs.append(self.act_batch(chunk, hi - lo, deterministic, sess, sub_expired))
                if expired_out is not None and sub_expired:
                    expired_out.extend(lo + i for i in sub_expired)
            return np.concatenate(outs, axis=0)

        bucket = _bucket_for(n, self.buckets)
        params, _ = self.current_params()
        state = None
        sess_list: List[Optional[str]] = list(sessions) if sessions is not None else []
        expired_idx: set = set()
        if self.core.stateful:
            rows = []
            for i in range(n):
                sid = sess_list[i] if i < len(sess_list) else None
                row = self.sessions.get(sid) if sid is not None else None
                if (
                    row is None
                    and sid is not None
                    and expired_out is not None
                    and self.sessions.expired(sid)
                ):
                    expired_idx.add(i)
                rows.append(row if row is not None else self._init_row)
            rows.extend([self._init_row] * (bucket - n))
            state = self._stack_rows(rows)
        padded = self._pad(obs, n, bucket)
        with self._act_lock:
            actions, new_state, new_key = self._jit_variants[bool(deterministic)](
                params, padded, state, self._key
            )
            self._key = new_key
        actions_np = np.asarray(jax.device_get(actions))[:n]
        if self.core.stateful and new_state is not None:
            host_state = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), new_state)
            for i in range(n):
                sid = sess_list[i] if i < len(sess_list) else None
                if sid is not None and i not in expired_idx:
                    self.sessions.put(sid, jax.tree.map(lambda x: x[i : i + 1], host_state))
        if expired_out is not None:
            expired_out.extend(sorted(expired_idx))
        return actions_np

    def act(
        self,
        raw_obs: Dict[str, Any],
        deterministic: bool = False,
        session: Optional[str] = None,
    ) -> np.ndarray:
        """Single-request convenience path (evaluation, in-process clients):
        prepare → act_batch(1) → the [1, ...] action array."""
        prepared = self.prepare(raw_obs, 1)
        return self.act_batch(prepared, 1, deterministic=deterministic, sessions=[session])
