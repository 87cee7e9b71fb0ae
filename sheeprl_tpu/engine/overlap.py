"""The in-process sources of packets: a player thread beside the learner
(concurrent acting + training with bounded staleness), or the same player
inline on the learner's thread.

An algorithm's ``main`` has ONE learner loop, written against the source
protocol: ``start(play)``, ``take(max_packets=0) -> list`` (empty: ended), a
packet with ``env_steps`` and ``apply(rb, aggregator)``, ``published(...)``
once per iteration that consumed packets, ``burst``, and ``shutdown(absorb)
-> drained env steps``. :class:`OverlapEngine` answers it twice, selected by
``algo.overlap.enabled``, and :class:`sheeprl_tpu.fleet.FleetEngine` a third
time with worker processes (``algo.fleet.workers > 0``).

**Inline** (``enabled`` false): ``take()`` runs ``play`` once on the caller's
thread and returns that one packet. Env interaction and gradient bursts then
interleave in one thread: the device idles while Python steps environments,
and the player acts with the params the last iteration refreshed. No thread,
no ring, no gate, no ``overlap`` event; tests use it as the reference of the
other two sources.

**Threaded** (``enabled`` true) is the Podracer/Sebulba split (arXiv:
2104.06272), re-derived for a single-controller JAX process. (The
multi-PROCESS twin of this split lives in the actor fleet: under
``fleet.act_mode=inference`` the workers ship obs batches to the
learner-hosted batched act service — :mod:`sheeprl_tpu.fleet.act_service` —
and for jax-native envs :mod:`sheeprl_tpu.fleet.anakin` fuses env + policy
under one jitted scan, the Anakin corner of the same paper.)

* the **player thread** steps the envs, acting against the existing
  :class:`~sheeprl_tpu.parallel.placement.ParamMirror` snapshot — on a
  multi-device mesh its jitted ``act`` is pinned to the mirror device, so
  act dispatches stop contending with the train burst's device stream; on a
  single device this degrades to overlapping host-side env stepping with
  the learner's async device compute;
* the **learner thread** (the caller) drains transitions from a bounded
  SPSC queue into the replay buffer / prefetcher and runs the scanned
  gradient bursts;
* **staleness is bounded to one burst**: the player always acts with the
  latest *published* params, so the only staleness is the burst currently
  in flight on the learner (packets record it; the gate enforces the
  configured bound if a future learner ever pipelines bursts);
* **replay-ratio accounting is exact**: the learner feeds the `Ratio`
  controller one call per acknowledged packet, in FIFO order, with the
  same ``policy_step`` arguments the inline source leads to — the
  env-step:grad-step ledger is bit-identical to the inline run's.

Integration contract (what each adopted algorithm provides):

* a ``play_fn()`` closure — ONE env-interaction slice (one vector step for
  Dreamer/SAC, one full rollout for PPO) that records its replay-buffer
  mutations into a :class:`RecordingSink` and returns a :class:`Packet`;
* an ``absorb(packet)`` learner-side apply (usually ``packet.apply(rb)``);
* ``engine.published()`` after the train burst + mirror refresh, so the
  engine can account staleness and stalls.

`RunGuard` integration: the player stops feeding as soon as preemption is
requested (its queue waits poll ``guard.preempted``); the learner breaks at
its own ``guard.stop_reached`` boundary, finishes the in-flight burst, and
``engine.shutdown(absorb)`` joins the player and drains the queued
transitions into the buffer so the final checkpoint sees a consistent
buffer (policy-step counter == buffer content; the replay-ratio controller
catches up on resume).

Telemetry: the engine emits ``overlap`` JSONL events (player-stall /
learner-stall / queue-depth / staleness) through the run's event stream.
It times with spans and nothing else, so the same intervals lie in a
profiler capture: each env slice under the usual
``Time/env_interaction_time`` (counts: ``env_steps``, and ``version``, the
published params it started with), the player blocked on a full queue or the
staleness gate under ``Wait/player_queue``, the learner blocked on an empty
queue under ``Wait/learner_queue`` (count: ``packets`` it then took). The
event's busy and stall seconds are the elapsed time of those spans.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ..telemetry.spans import Span

__all__ = ["OverlapEngine", "Packet", "RecordingSink", "SpscRing", "telem_span"]


class SpscRing:
    """Bounded single-producer / single-consumer ring queue.

    Lock-free on the data path: the producer only writes ``_tail``, the
    consumer only writes ``_head``; CPython attribute stores/loads of ints
    are atomic under the GIL, so no lock is needed for correctness. Blocking
    behaviour (with stall accounting and cooperative stop) lives in the
    engine, built on the non-blocking ``try_put``/``try_get``.
    """

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self._cap = int(capacity) + 1  # one slot sacrificed to tell full/empty
        self._buf: List[Any] = [None] * self._cap
        self._head = 0  # next slot to read (consumer-owned)
        self._tail = 0  # next slot to write (producer-owned)

    def __len__(self) -> int:
        return (self._tail - self._head) % self._cap

    @property
    def capacity(self) -> int:
        return self._cap - 1

    def try_put(self, item: Any) -> bool:
        nxt = (self._tail + 1) % self._cap
        if nxt == self._head:
            return False  # full
        self._buf[self._tail] = item
        self._tail = nxt  # publish AFTER the slot is written
        return True

    def try_get(self) -> Any:
        """The next item, or the ring itself as a 'empty' sentinel (None is
        a legal item)."""
        head = self._head
        if head == self._tail:
            return self
        item = self._buf[head]
        self._buf[head] = None  # drop the ref so payloads don't linger
        self._head = (head + 1) % self._cap
        return item


class Packet:
    """One env-interaction slice crossing the player→learner queue."""

    __slots__ = (
        "payload",
        "env_steps",
        "version",
        "staleness",
        "produced_t",
        "produced_step",
        "produced_wall",
        "trace_id",
        "span_id",
    )

    def __init__(self, payload: Any, env_steps: int):
        self.payload = payload
        self.env_steps = int(env_steps)
        self.version = 0  # published-params version the player acted with
        self.staleness = 0  # bursts in flight at production time (≤ bound)
        self.produced_t = 0.0
        self.produced_step = 0  # player env-step counter AFTER this slice
        self.produced_wall = 0.0  # wall clock at production (trace axis)
        # distributed-trace identity: the player stamps a fresh trace per
        # packet; the learner's take/apply spans join it, so one packet's
        # env-step → queue → apply path is reconstructable cross-thread
        # exactly like a fleet packet's is cross-process
        self.trace_id = ""
        self.span_id = ""

    # -- replay-buffer op payloads ----------------------------------------
    def apply(self, rb: Any, aggregator: Any = None) -> None:
        """Apply a :class:`RecordingSink` op-list payload (buffer ops +
        deferred episode stats) to ``rb`` in production order (no-op for
        non-op payloads)."""
        if isinstance(self.payload, RecordingSink):
            self.payload.apply(rb, aggregator)


class RecordingSink:
    """Records replay-buffer mutations player-side, to be applied
    learner-side in the same order.

    ``add`` **copies** its arrays: the interaction closures reuse/mutate
    their ``step_data`` dicts across iterations (and gymnasium vector envs
    reuse their obs buffers in place), and the learner may apply the op well
    after the player has moved on. The copy is the price of the handoff,
    and the inline source pays it too: one closure, one kind of packet.

    ``stat`` records metric updates (episode reward/length) for the same
    deferred apply: the aggregator has no locking, so all of its writes
    must stay on the learner thread.
    """

    __slots__ = ("ops", "stats")

    def __init__(self) -> None:
        self.ops: List[tuple] = []
        self.stats: List[tuple] = []

    def add(self, data: Dict[str, np.ndarray], idxes: Any = None, validate_args: bool = False) -> None:
        self.ops.append(
            ("add", {k: np.array(v, copy=True) for k, v in data.items()}, idxes, validate_args)
        )

    def mark_restart(self, env_idx: int) -> None:
        self.ops.append(("restart", int(env_idx), None, False))

    def stat(self, key: str, value: Any) -> None:
        self.stats.append((key, value))

    def apply(self, rb: Any, aggregator: Any = None) -> None:
        for op, a, idxes, validate in self.ops:
            if op == "add":
                if idxes is None:
                    rb.add(a, validate_args=validate)
                else:
                    rb.add(a, idxes, validate_args=validate)
            elif hasattr(rb, "mark_restart"):
                rb.mark_restart(a)
        if aggregator is not None:
            for key, value in self.stats:
                aggregator.update(key, value)
        self.ops = []
        self.stats = []


_SLEEP_S = 0.0005  # park granularity for a blocked side (≪ one env step)


def telem_span(telem: Any, name: str, **counts: float) -> Span:
    """The run's span where ``telem`` is its facade (it honours
    `metric.disable_timer`), else a plain one on the global tracker."""
    make = getattr(telem, "span", None)
    return make(name, **counts) if make is not None else Span(name, **counts)


class OverlapEngine:
    """The in-process source of packets: a player thread with bounded
    staleness when ``enabled``, else the same ``play`` inline on the caller's
    thread (module docstring). Construct via :meth:`setup`.
    """

    def __init__(
        self,
        *,
        enabled: bool = True,
        queue_depth: int = 4,
        staleness_bound: int = 1,
        stats_every_s: float = 5.0,
        total_steps: int = 0,
        initial_step: int = 0,
        telem: Any = None,
        guard: Any = None,
        trace_spans: bool = True,
    ) -> None:
        self.enabled = bool(enabled)
        self.queue_depth = max(1, int(queue_depth))
        # 0 is legal and means STRICT freshness: the player may not act while
        # any burst is unpublished. Publishing happens right after the burst's
        # async dispatch (not its device completion), so the player unblocks
        # in microseconds and env stepping still overlaps device execution —
        # this is the on-policy (PPO) mode: trajectories are bitwise-identical
        # to the inline source's, because the acting params are exactly the
        # latest update's.
        self.staleness_bound = max(0, int(staleness_bound))
        self.stats_every_s = float(stats_every_s)
        self.total_steps = int(total_steps)
        self.initial_step = int(initial_step)
        self.telem = telem
        self.guard = guard
        self.trace_spans = bool(trace_spans) and telem is not None

        self._ring = SpscRing(self.queue_depth)
        self._stop = threading.Event()
        self._player_done = threading.Event()
        self._player_exc: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        self._play: Optional[Callable[[], Optional[Packet]]] = None

        # learner-owned counters (GIL-atomic int stores; the player only reads)
        self._burst_seq = 0  # bursts started
        self._pub_seq = 0  # bursts whose params the mirror has published
        self.acked_steps = 0  # env steps handed to the learner
        # player-owned counters (the learner only reads)
        self.produced_steps = 0
        self.packets_produced = 0

        # interval stats (reset at each emit)
        self._stats_lock = threading.Lock()
        self._player_busy_s = 0.0
        self._player_stall_s = 0.0
        self._learner_stall_s = 0.0
        self._staleness_max = 0
        self.staleness_seen_max = 0  # whole-run high-water mark (tests)
        self._last_emit_t = time.perf_counter()
        self._events = 0

    # -- construction ------------------------------------------------------
    @classmethod
    def setup(
        cls,
        cfg: Any,
        telem: Any = None,
        guard: Any = None,
        *,
        total_steps: int,
        initial_step: int = 0,
        default_queue_depth: int = 4,
    ) -> "OverlapEngine":
        sel = cfg.select if hasattr(cfg, "select") else (lambda p, d=None: d)
        # NOTE: no `or default` coercion — 0 is a meaningful staleness bound
        # (strict on-policy mode), only None means "not configured"
        sb = sel("algo.overlap.staleness_bound", 1)
        se = sel("algo.overlap.stats_every_s", 5.0)
        return cls(
            enabled=bool(sel("algo.overlap.enabled", False)),
            queue_depth=int(sel("algo.overlap.queue_depth", default_queue_depth) or default_queue_depth),
            staleness_bound=int(1 if sb is None else sb),
            stats_every_s=float(5.0 if se is None else se),
            total_steps=total_steps,
            initial_step=initial_step,
            telem=telem,
            guard=guard,
            trace_spans=bool(sel("metric.telemetry.trace_spans", True)),
        )

    # -- lifecycle ---------------------------------------------------------
    def start(self, play_fn: Callable[[], Optional[Packet]]) -> "OverlapEngine":
        """Keep ``play_fn`` (one env slice per call: a Packet, or None to stop
        early) and, when ``enabled``, spawn the player thread that calls it;
        inline, :meth:`take` calls it."""
        if self._play is not None:
            return self
        self._play = play_fn
        self.produced_steps = self.initial_step
        self.acked_steps = self.initial_step
        if self.enabled:
            self._thread = threading.Thread(
                target=self._player_main, args=(play_fn,), name="overlap-player", daemon=True
            )
            self._thread.start()
        return self

    @property
    def run_ahead(self) -> int:
        """Packets the player may have produced beyond the one the learner
        holds: a payload that is handed over without a copy (PPO's rollout
        buffer) needs that many spares."""
        return self.queue_depth if self.enabled else 0

    def _should_stop(self) -> bool:
        if self._stop.is_set():
            return True
        g = self.guard
        return g is not None and getattr(g, "preempted", False)

    def _span(self, name: str, **counts: float) -> Span:
        return telem_span(self.telem, name, **counts)

    def _book(self, player_busy_s: float = 0.0, player_stall_s: float = 0.0, learner_stall_s: float = 0.0) -> None:
        """A span's elapsed seconds into the interval's `overlap` event."""
        with self._stats_lock:
            self._player_busy_s += player_busy_s
            self._player_stall_s += player_stall_s
            self._learner_stall_s += learner_stall_s

    def _player_gated(self) -> bool:
        return (
            len(self._ring) >= self._ring.capacity
            or self._burst_seq - self._pub_seq > self.staleness_bound
        )

    def _player_main(self, play_fn: Callable[[], Optional[Packet]]) -> None:
        try:
            while not self._should_stop() and (
                self.total_steps <= 0 or self.produced_steps < self.total_steps
            ):
                # Backpressure BEFORE acting, not after: wait for a free
                # queue slot and for the staleness gate, THEN collect the
                # slice. Waiting after collection would let the player act
                # one slice beyond the bound with params one publish older
                # than intended (e.g. PPO would collect rollout k+2 with
                # params k-1 while update k is still running). The staleness
                # gate itself (never act more than `staleness_bound` bursts
                # behind the latest published params) cannot block with a
                # synchronous learner and bound 1 — it is the enforced
                # contract, the queue bound is the steady-state throttle.
                if self._player_gated():
                    with self._span("Wait/player_queue") as gate:
                        while self._player_gated() and not self._should_stop():
                            time.sleep(_SLEEP_S)
                    self._book(player_stall_s=gate.elapsed)
                if self._should_stop():
                    break

                t0_wall = time.time()
                with self._span("Time/env_interaction_time", version=self._pub_seq) as busy:
                    pkt = play_fn()
                    if pkt is not None:
                        busy.count(env_steps=pkt.env_steps)
                self._book(player_busy_s=busy.elapsed)
                if pkt is None:
                    break
                pkt.version = self._pub_seq
                pkt.staleness = self._burst_seq - self._pub_seq
                pkt.produced_t = time.perf_counter()
                pkt.produced_wall = time.time()
                # step-id stamp: the player's env-step counter once this
                # slice lands — diag correlates player/learner spans with it
                pkt.produced_step = self.produced_steps + pkt.env_steps
                if self.trace_spans:
                    # the packet's trace identity: the learner's take span
                    # joins it, same contract as a fleet packet's frame
                    from ..telemetry import tracing

                    pkt.trace_id = tracing.new_trace_id()
                    pkt.span_id = tracing.new_span_id()
                    try:
                        self.telem.emit(
                            tracing.span_record(
                                "env_step",
                                "player",
                                tracing.TraceContext(pkt.trace_id, pkt.span_id),
                                t0_wall,
                                pkt.produced_wall,
                                step=pkt.produced_step,
                                version=pkt.version,
                            )
                        )
                    except Exception:
                        pass

                # sole producer + pre-checked free slot: effectively
                # immediate (the loop only guards the engine's invariants)
                put = self._ring.try_put(pkt)
                if not put:
                    with self._span("Wait/player_queue") as full:
                        while not put and not self._should_stop():
                            time.sleep(_SLEEP_S)
                            put = self._ring.try_put(pkt)
                    self._book(player_stall_s=full.elapsed)
                    if not put:
                        return  # stop requested while blocked on a full queue

                self.produced_steps += pkt.env_steps
                self.packets_produced += 1
                with self._stats_lock:
                    if pkt.staleness > self._staleness_max:
                        self._staleness_max = pkt.staleness
                    if pkt.staleness > self.staleness_seen_max:
                        self.staleness_seen_max = pkt.staleness
        except BaseException as e:  # surfaced on the learner's next take()
            self._player_exc = e
        finally:
            self._player_done.set()

    # -- learner side ------------------------------------------------------
    def take(self, max_packets: int = 0) -> List[Packet]:
        """Drain available packets (blocking for the first one). Returns []
        when the player is done/stopped and the queue is empty — the learner
        loop should break then. Raises if the player thread crashed.

        A non-empty return CLAIMS a burst slot against the staleness gate;
        the learner must release it with :meth:`published` once per
        iteration (after the mirror refresh, if any training ran). The
        claim is taken BEFORE the first packet leaves the ring, so between
        a packet landing and its update publishing, a strict
        (``staleness_bound=0``) player is always held by either the queue
        bound or the claim — there is no instant where it could start
        acting with pre-update params.

        Inline, there is nothing to drain: one ``play`` on this thread, under
        the same span with the same counts, is the one packet (``[]`` once a
        stop is requested or ``play`` returns None; what ``play`` raises,
        raises here)."""
        if not self.enabled:
            return self._take_inline()
        out: List[Packet] = []
        claimed = False

        def drain() -> None:
            nonlocal claimed
            while len(self._ring) > 0 and not (max_packets and len(out) >= max_packets):
                if not claimed:
                    claimed = True
                    self._burst_seq += 1  # claim BEFORE the pop (see docstring)
                item = self._ring.try_get()
                if item is not self._ring:
                    out.append(item)

        def fed_or_ended() -> bool:
            return bool(out) or self._player_exc is not None or self._player_done.is_set() or self._should_stop()

        drain()
        if not fed_or_ended():
            with self._span("Wait/learner_queue") as wait:
                while not fed_or_ended():
                    time.sleep(_SLEEP_S)
                    drain()
                wait.count(packets=len(out))
            self._book(learner_stall_s=wait.elapsed)
        if not out:
            drain()  # a last packet put just before the player set its done flag
        if self._player_exc is not None and not out:
            raise RuntimeError("overlap player thread crashed") from self._player_exc
        now_wall = time.time()
        for pkt in out:
            self.acked_steps += pkt.env_steps
            if self.trace_spans and pkt.trace_id:
                # queue transit: production → learner pickup, continuing the
                # packet's trace (the fleet twin is the worker's queue_wait)
                from ..telemetry import tracing

                try:
                    self.telem.emit(
                        tracing.span_record(
                            "queue_wait",
                            "learner",
                            tracing.TraceContext(pkt.trace_id, tracing.new_span_id(), pkt.span_id),
                            pkt.produced_wall,
                            now_wall,
                            step=self.acked_steps,
                        )
                    )
                except Exception:
                    pass
        self.maybe_emit()
        return out

    def _take_inline(self) -> List[Packet]:
        if self._should_stop():
            return []
        with self._span("Time/env_interaction_time", version=self._pub_seq) as busy:
            pkt = self._play()
            if pkt is not None:
                busy.count(env_steps=pkt.env_steps)
        if pkt is None:
            return []
        self._burst_seq += 1  # the same claim counter: `burst` means one thing
        pkt.version = self._pub_seq
        self.acked_steps += pkt.env_steps  # the player-owned counters stay the thread's
        return [pkt]

    def burst_started(self) -> None:
        """Claim an EXTRA burst slot (a pipelined learner dispatching more
        than one unpublished burst); ``take()`` already claims one per
        non-empty drain, so synchronous learners never call this."""
        self._burst_seq += 1

    def published(self, snapshot: Any = None) -> None:
        """Release the claim(s): the iteration's params are published (call
        after ``mirror.refresh`` when training ran — once per learner
        iteration that consumed packets). ``snapshot``, the refreshed params
        when training ran, is for a source whose players live elsewhere (the
        fleet broadcasts it); this one's player reads the mirror itself."""
        self._pub_seq = self._burst_seq

    @property
    def burst(self) -> int:
        """The claim the learner holds: the `burst` count of its
        `Time/train_time` spans, against the `version` of the player's
        `Time/env_interaction_time` (a slice of version v started before the
        params of burst v + 1 were published)."""
        return self._burst_seq

    @property
    def queue_len(self) -> int:
        return len(self._ring)

    # -- telemetry ---------------------------------------------------------
    def maybe_emit(self, force: bool = False) -> Optional[Dict[str, Any]]:
        if self.telem is None or not self.enabled:
            return None
        now = time.perf_counter()
        elapsed = now - self._last_emit_t
        if not force and elapsed < self.stats_every_s:
            return None
        with self._stats_lock:
            busy, pstall, lstall = self._player_busy_s, self._player_stall_s, self._learner_stall_s
            stale_max = self._staleness_max
            self._player_busy_s = self._player_stall_s = self._learner_stall_s = 0.0
            self._staleness_max = 0
        self._last_emit_t = now
        denom = busy + pstall
        rec = {
            "event": "overlap",
            "step": int(self.acked_steps),
            "player_step": int(self.produced_steps),
            "queue_depth": int(len(self._ring)),
            "queue_cap": int(self.queue_depth),
            "packets": int(self.packets_produced),
            "bursts": int(self._pub_seq),
            "env_steps_ahead": int(self.produced_steps - self.acked_steps),
            "player_busy_s": round(busy, 6),
            "player_stall_s": round(pstall, 6),
            "learner_stall_s": round(lstall, 6),
            "player_stall_frac": round(pstall / denom, 6) if denom > 0 else 0.0,
            "staleness_max": int(stale_max),
            "interval_s": round(elapsed, 6),
        }
        try:
            self.telem.emit(rec)
            self._events += 1
        except Exception:
            pass
        return rec

    # -- shutdown ----------------------------------------------------------
    def shutdown(self, absorb: Optional[Callable[[Packet], None]] = None, timeout: float = 60.0) -> int:
        """Stop the player, join it, and drain queued packets through
        ``absorb`` (learner-side buffer apply) so the final checkpoint sees
        every transition that crossed the queue. Returns the env steps
        drained. Safe to call twice; inline, nothing is queued: 0."""
        if not self.enabled:
            return 0
        self._stop.set()
        thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=timeout)
        drained = 0
        while True:
            item = self._ring.try_get()
            if item is self._ring:
                break
            self.acked_steps += item.env_steps
            if absorb is not None:
                absorb(item)
                drained += item.env_steps
        self.maybe_emit(force=True)
        return drained
