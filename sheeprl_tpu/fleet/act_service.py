"""`ActService` — learner-hosted batched acting for the fleet (Sebulba).

The Podracer **Sebulba** layout (arXiv:2104.06272): under
``fleet.act_mode=inference`` the workers stop running per-process host-CPU
policy steps and instead ship observation batches (plus the base PRNG key of
the slice) to one service living in the learner process, where the
algorithm's :mod:`~sheeprl_tpu.fleet.act_core` steps the whole fleet's rows
in one bucketed jitted call on the learner's accelerator. The serve stack's
machinery is reused wholesale: deadline-coalescing flush loop
(`serve.batcher.MicroBatcher` idiom), power-of-two bucket padding
(`serve.policy._bucket_for`), per-session recurrent state rows
(`serve.policy.SessionStore` keyed ``"{worker_id}/{env_slot}"``) and
`serve.batcher.ServeStats` (occupancy + pad-waste observability).

Parity is the contract, not an aspiration: the service calls the SAME
jitted core a worker-mode program calls locally, with per-row keys
recomputed from the shipped base key (``act_core.row_keys``), so a row
acted remotely is the same function of (params, obs, key, state) as the
row acted on the worker host, whatever it was padded or coalesced with.
In bits: the same rows with the same keys at the same bucket width give
the same bits. Across widths the compiler may vectorise the same math
differently, and the last bit of a float can move (it does for SAC's tanh
on this XLA:CPU, and a TPU tiles matmuls by width), so a worker's own
narrower call agrees to float32 rounding only. The act-parity test pins
both statements for SAC and DV3.

Durability properties:

* **idempotent requests** — a worker re-sends an unanswered request (lost
  response on a link drop); the service caches the last completed
  ``(req_id, response)`` per ``(worker_id, incarnation)`` and answers
  retries from the cache WITHOUT re-stepping recurrent latents, and drops
  duplicates of a request still in flight.
* **latent migration on respawn** — session rows are keyed by worker id
  (not incarnation); a respawned program's first request carries a
  full reset mask, so its rows re-initialize in the same publication-
  versioned state a fresh worker-mode player would start from.
* **publication coupling** — :meth:`swap_params` is called by
  `FleetEngine.publish` with the NEXT ledger version *before* the
  supervisor broadcasts to workers, so by the time any worker learns of
  publication N the service already acts with it: staleness accounting
  stays identical to the per-worker path.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Set, Tuple

import numpy as np

from ..serve.batcher import ServeStats
from ..serve.policy import DEFAULT_BUCKETS, SessionStore, _bucket_for
from .act_core import ActCore, build_act_core, row_keys

__all__ = ["ActService"]


class _ActJob:
    __slots__ = ("req", "reply", "t_submit")

    def __init__(self, req: Dict[str, Any], reply: Callable[[Dict[str, Any]], None]) -> None:
        self.req = req
        self.reply = reply
        self.t_submit = time.monotonic()


def _concat_rows(trees: List[Any]) -> Any:
    import jax

    return jax.tree.map(
        lambda *xs: np.concatenate([np.asarray(x) for x in xs], axis=0), *trees
    )


def _pad_rows(tree: Any, n: int, bucket: int) -> Any:
    if bucket == n:
        return tree
    import jax

    def pad_leaf(x: Any) -> np.ndarray:
        x = np.asarray(x)
        pad = np.zeros((bucket - n,) + x.shape[1:], x.dtype)
        return np.concatenate([x, pad], axis=0)

    return jax.tree.map(pad_leaf, tree)


class ActService:
    """One batched act endpoint for the whole fleet, hosted by the learner.

    Request (a plain dict — it rides both transports):
    ``{worker_id, incarnation, req_id, n, obs, key, reset?, mask?, trace?}``
    where ``obs`` is the program's prepared obs tree with leading dim ``n``,
    ``key`` the slice's base PRNG key (uint32 pair) and ``reset`` an
    optional ``bool[n]`` mask of env slots whose latent must re-initialize
    (dones/restarts/respawn). Response:
    ``{req_id, version, actions, actions_cat?}`` or ``{req_id, error}``.
    """

    def __init__(self, cfg: Any, program: str, telem: Any = None, trace: bool = True) -> None:
        sel = cfg.select if hasattr(cfg, "select") else (lambda p, d=None: d)

        def opt(path: str, default: Any) -> Any:
            v = sel(path, None)
            return default if v is None else v

        self.cfg = cfg
        self.program = str(program)
        self.telem = telem
        self.trace = bool(trace)
        self.max_wait_s = max(0.0, float(opt("fleet.act.max_wait_ms", 5.0)) / 1000.0)
        raw = list(opt("fleet.act.buckets", None) or DEFAULT_BUCKETS)
        self.buckets: List[int] = sorted({int(b) for b in raw})
        if any(b <= 0 for b in self.buckets):
            raise ValueError(f"fleet.act.buckets must be positive, got {self.buckets}")
        self.sessions = SessionStore(int(opt("fleet.act.max_sessions", 4096)))
        from ..diag.prometheus import Registry

        self.stats = ServeStats(registry=Registry(prefix="sheeprl_fleet_act"))
        self.core: Optional[ActCore] = None
        self._params: Any = None
        self._version = 0
        self._staged: Optional[Tuple[Any, int]] = None  # publication before start()
        self._init_row: Any = None
        self._params_lock = threading.Lock()
        self._act_lock = threading.Lock()
        self._cv = threading.Condition()
        self._pending: Deque[_ActJob] = deque()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._pump: Optional[threading.Thread] = None
        self._sup: Any = None
        # (worker_id, incarnation) -> (req_id, response) of the LAST completed
        # request — the retry/idempotency cache (latents step exactly once)
        self._done: Dict[Tuple[int, int], Tuple[int, Dict[str, Any]]] = {}
        self._inflight: Set[Tuple[int, int, int]] = set()

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "ActService":
        if self.core is None:
            from ..utils.env import probe_env_spaces

            sel = self.cfg.select if hasattr(self.cfg, "select") else (lambda p, d=None: d)
            obs_space, action_space = probe_env_spaces(
                self.cfg, int(sel("seed", 0) or 0), 0
            )
            self.core = build_act_core(self.program, self.cfg, obs_space, action_space)
            if self._staged is not None:
                params_np, version = self._staged
                self._staged = None
                self.swap_params(params_np, version)
        if self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._flush_loop, daemon=True, name="fleet-act-service"
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        for t in (self._thread, self._pump):
            if t is not None:
                t.join(timeout=5.0)
        self._thread = None
        self._pump = None
        with self._cv:
            leftovers = list(self._pending)
            self._pending.clear()
            self._inflight.clear()
        # fail whatever is still queued so no worker parks on a dead service
        for job in leftovers:
            try:
                job.reply(
                    {"req_id": int(job.req.get("req_id", 0)), "error": "act service shut down"}
                )
            except Exception:
                pass

    # -- param publication coupling ----------------------------------------
    def swap_params(self, params_np: Any, version: int) -> None:
        """Install one publication's acting subtree (device-put once, swapped
        under the lock — the double-buffered `InferencePolicy.swap_params`
        idiom). Called BEFORE the supervisor broadcasts the same version, so
        no worker can act through the service with params older than the
        publication it was just told about."""
        if self.core is None:
            self._staged = (params_np, int(version))
            return
        import jax

        new = jax.device_put(self.core.extract_params(params_np))
        for leaf in jax.tree.leaves(new):
            getattr(leaf, "block_until_ready", lambda: None)()
        init_row = None
        if self.core.stateful:
            row = self.core.init_state(new, 1)
            init_row = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), row)
        with self._params_lock:
            self._params = new
            self._version = int(version)
            if init_row is not None:
                self._init_row = init_row

    @property
    def version(self) -> int:
        with self._params_lock:
            return self._version

    # -- transports --------------------------------------------------------
    def wire_handler(self, chan: Any, req: Dict[str, Any]) -> None:
        """Socket-transport entry: `LearnerChannel` calls this per T_ACT
        frame; the response rides back as T_ACT_RESP on the same link."""
        self.submit(req, chan.send_act_resp)

    def attach_mp(self, sup: Any) -> None:
        """mp-transport entry: a pump thread sweeps every handle's
        ``act_req`` queue and replies into the same channel's ``act_resp``
        queue (captured at dequeue time — a respawned incarnation's fresh
        channel is picked up on the next sweep, stale replies go to the dead
        queue and are simply never read)."""
        if self._pump is not None:
            return
        self._sup = sup
        self._pump = threading.Thread(
            target=self._pump_loop, daemon=True, name="fleet-act-mp-pump"
        )
        self._pump.start()

    def _pump_loop(self) -> None:
        import queue as _q

        while not self._stop.is_set():
            got = False
            sup = self._sup
            handles = list(getattr(sup, "handles", []) or [])
            for h in handles:
                ch = h.channel
                q = getattr(ch, "act_req", None) if ch is not None else None
                if q is None:
                    continue
                for _ in range(64):
                    try:
                        req = q.get_nowait()
                    except _q.Empty:
                        break
                    except Exception:
                        break
                    got = True
                    resp_q = ch.act_resp

                    def _reply(resp: Dict[str, Any], _rq: Any = resp_q) -> None:
                        try:
                            _rq.put_nowait(resp)
                        except Exception:
                            pass  # dead incarnation's queue: monitor owns it

                    self.submit(req, _reply)
            if not got:
                time.sleep(0.001)

    # -- submission --------------------------------------------------------
    def submit(self, req: Dict[str, Any], reply: Callable[[Dict[str, Any]], None]) -> None:
        wid = int(req.get("worker_id", -1))
        inc = int(req.get("incarnation", 0))
        rid = int(req.get("req_id", 0))
        cached: Optional[Dict[str, Any]] = None
        with self._cv:
            done = self._done.get((wid, inc))
            if done is not None and done[0] == rid:
                cached = done[1]  # a retry for a lost response: answer, don't re-step
            elif (wid, inc, rid) in self._inflight:
                return  # duplicate of an in-flight request: the original will answer
            else:
                self._inflight.add((wid, inc, rid))
                self._pending.append(_ActJob(req, reply))
                self.stats.record_submit()
                self._cv.notify_all()
        if cached is not None:
            try:
                reply(cached)
            except Exception:
                pass

    @property
    def queue_depth(self) -> int:
        with self._cv:
            return len(self._pending)

    # -- the flush loop ----------------------------------------------------
    def _rows_pending_locked(self) -> int:
        return sum(int(job.req.get("n", 0)) for job in self._pending)

    def _take_batch_locked(self) -> List[_ActJob]:
        """Head-of-queue run of requests whose rows fit the widest bucket
        (a request wider than the bucket rides alone — padded to its own
        power of two). Requests with/without an action mask never coalesce:
        their jitted variants differ."""
        max_rows = self.buckets[-1]
        batch: List[_ActJob] = []
        rows = 0
        while self._pending:
            job = self._pending[0]
            n = int(job.req.get("n", 0))
            if batch:
                if rows + n > max_rows:
                    break
                if (job.req.get("mask") is None) != (batch[0].req.get("mask") is None):
                    break
            batch.append(self._pending.popleft())
            rows += n
        return batch

    def _flush_loop(self) -> None:
        while not self._stop.is_set():
            with self._cv:
                while not self._pending and not self._stop.is_set():
                    self._cv.wait(timeout=0.1)
                if self._stop.is_set():
                    return
                # deadline flush: max_wait_s from the OLDEST request to fill
                # the widest bucket, then act with what's there
                deadline = self._pending[0].t_submit + self.max_wait_s
                while (
                    self._rows_pending_locked() < self.buckets[-1]
                    and not self._stop.is_set()
                    and time.monotonic() < deadline
                ):
                    self._cv.wait(timeout=max(0.0, deadline - time.monotonic()))
                batch = self._take_batch_locked()
            if batch:
                self._run_batch(batch)

    def _bucket(self, total: int) -> int:
        if total <= self.buckets[-1]:
            return _bucket_for(total, self.buckets)
        # beyond the configured buckets: the next power of two, so one
        # oversized fleet layout costs one extra trace, not one per width
        return 1 << (int(total) - 1).bit_length()

    def _run_batch(self, jobs: List[_ActJob]) -> None:
        import jax

        with self._params_lock:
            params, version, init_row = self._params, self._version, self._init_row
        core = self.core
        if params is None or core is None:
            # workers gate on learning_starts before first publication, so
            # this is a protocol violation, not a routine state
            for job in jobs:
                self._finish(
                    job,
                    {
                        "req_id": int(job.req.get("req_id", 0)),
                        "error": "act service has no published params yet",
                    },
                    error=True,
                )
            return
        t0_wall = time.time()
        t0 = time.monotonic()
        ns = [int(job.req["n"]) for job in jobs]
        total = sum(ns)
        bucket = self._bucket(total)
        try:
            obs = _pad_rows(_concat_rows([job.req["obs"] for job in jobs]), total, bucket)
            keys = [np.asarray(jax.device_get(row_keys(np.asarray(job.req["key"]), n)))
                    for job, n in zip(jobs, ns)]
            if bucket > total:
                keys.append(np.zeros((bucket - total,) + keys[0].shape[1:], keys[0].dtype))
            keys_np = np.concatenate(keys, axis=0)
            state = None
            if core.stateful:
                rows: List[Any] = []
                for job, n in zip(jobs, ns):
                    wid = int(job.req["worker_id"])
                    reset = job.req.get("reset")
                    for slot in range(n):
                        row = None
                        if reset is None or not bool(np.asarray(reset).reshape(-1)[slot]):
                            row = self.sessions.get(f"{wid}/{slot}")
                        rows.append(row if row is not None else init_row)
                rows.extend([init_row] * (bucket - total))
                state = _concat_rows(rows)
            mask = None
            if jobs[0].req.get("mask") is not None:
                mask = _concat_rows([job.req["mask"] for job in jobs])
                if bucket > total:
                    # padded mask rows repeat row 0 — their outputs are
                    # discarded, but the mask tree must keep the batch width
                    mask = jax.tree.map(
                        lambda x: np.concatenate(
                            [np.asarray(x)]
                            + [np.asarray(x)[:1]] * (bucket - total),
                            axis=0,
                        ),
                        mask,
                    )
            with self._act_lock:
                actions, actions_cat, new_state = core.act(
                    params, obs, keys_np, state=state, mask=mask
                )
            actions_np = np.asarray(jax.device_get(actions))[:total]
            cat_np = (
                np.asarray(jax.device_get(actions_cat))[:total]
                if actions_cat is not None
                else None
            )
            host_state = (
                jax.tree.map(lambda x: np.asarray(jax.device_get(x)), new_state)
                if new_state is not None
                else None
            )
        except BaseException as e:  # one bad request must not kill the learner
            for job in jobs:
                self._finish(
                    job,
                    {"req_id": int(job.req.get("req_id", 0)), "error": repr(e)},
                    error=True,
                )
            return
        dt = time.monotonic() - t0
        self.stats.record_batch(total, bucket, dt)
        t1_wall = time.time()
        off = 0
        for job, n in zip(jobs, ns):
            if host_state is not None:
                wid = int(job.req["worker_id"])
                for slot in range(n):
                    i = off + slot
                    self.sessions.put(
                        f"{wid}/{slot}", jax.tree.map(lambda x: x[i : i + 1], host_state)
                    )
            resp: Dict[str, Any] = {
                "req_id": int(job.req.get("req_id", 0)),
                "version": int(version),
                "actions": actions_np[off : off + n],
            }
            if cat_np is not None:
                resp["actions_cat"] = cat_np[off : off + n]
            self._finish(job, resp)
            self._emit_span(job, t0_wall, t1_wall, n, bucket, version)
            off += n

    def _finish(self, job: _ActJob, resp: Dict[str, Any], error: bool = False) -> None:
        ident = (int(job.req.get("worker_id", -1)), int(job.req.get("incarnation", 0)))
        rid = int(job.req.get("req_id", 0))
        with self._cv:
            self._inflight.discard(ident + (rid,))
            if not error:
                self._done[ident] = (rid, resp)
        self.stats.record_done(time.monotonic() - job.t_submit, error=error)
        try:
            job.reply(resp)
        except Exception:
            pass  # a dying link's reply: the worker's retry hits the cache

    def _emit_span(
        self, job: _ActJob, t0: float, t1: float, n: int, bucket: int, version: int
    ) -> None:
        """One `act_infer` span per request, joining the trace the worker's
        `act_submit` span opened — the pair is how `sheeprl_tpu trace` and
        the `act_service_starvation` finding attribute the new stage."""
        if not self.trace or self.telem is None:
            return
        tr = job.req.get("trace") or ("", "")
        if not tr or not tr[0]:
            return
        from ..telemetry import tracing

        try:
            self.telem.emit(  # lint: ok[hot-loop-emit] — one span per act request (same cadence as the worker's env_step spans)
                tracing.span_record(
                    "act_infer",
                    "learner",
                    tracing.TraceContext(str(tr[0]), tracing.new_span_id(), str(tr[1])),
                    t0,
                    t1,
                    worker=int(job.req.get("worker_id", -1)),
                    version=int(version),
                    detail=f"rows={n} bucket={bucket}",
                )
            )
        except Exception:
            pass

    # -- telemetry ---------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """The ``act_*`` fields the engine merges into its `fleet` interval
        record (schema'd; the starvation detector reads them)."""
        s = self.stats.snapshot()
        return {
            "act_requests": int(s["requests"]),
            "act_batches": int(s["batches"]),
            "act_occupancy": float(s["batch_occupancy"]),
            "act_pad_waste": float(s.get("pad_waste", 0.0)),
            "act_sessions": len(self.sessions),
            "act_version": self.version,
        }
