"""Host-side spans with device-trace annotations.

A `Span` is the telemetry replacement for the ad-hoc `utils.timer` context
manager: it accumulates wall-clock seconds into a thread-safe `SpanTracker`
AND (when profiling is possible) enters a `jax.profiler.TraceAnnotation` so
the same phase shows up on the device timeline in XProf/TensorBoard.

Design constraints:

* **thread safety** — decoupled runs time env interaction from the player
  thread and train time from the trainer thread into the same registry; the
  old class-global ``timer._timers`` dict raced and never drained.
* **drain semantics** — ``compute(reset=True)`` atomically snapshots and
  clears, so a log interval can never double-count a span that also ran
  during the previous interval.
* **nesting** — spans track a per-thread stack; a nested span records under
  its own name and knows its parent (exposed via `SpanTracker.counts`), so
  `Time/train_time` can contain `Time/train_time/prefetch` without either
  polluting the other's total.
* **counts** — keyword arguments of a span (``grad_steps=g``) are work done
  inside it: the tracker sums them per name beside seconds and calls, and the
  annotation carries them as stats of the event in a profiler capture, so a
  ratio is taken where the work happens and on the capture's clock.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Tuple


def _trace_annotation(name: str, counts: Dict[str, float]):
    """Best-effort jax.profiler.TraceAnnotation (None when jax is absent).
    The profiler carries ``counts`` inside the name (``name#k=v#``) and strips
    them into stats of the event when it writes the plane, so readers still
    find the event under its bare name."""
    try:
        import jax.profiler as _prof

        return _prof.TraceAnnotation(name, **counts)
    except Exception:
        return None


class SpanTracker:
    """Thread-safe name → (seconds, calls, summed counts) accumulator with
    drain semantics."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._totals: Dict[str, float] = {}
        self._counts: Dict[str, int] = {}
        self._sums: Dict[str, Dict[str, float]] = {}
        self._stack = threading.local()

    # -- per-thread nesting stack -----------------------------------------
    def _push(self, name: str) -> None:
        stack = getattr(self._stack, "names", None)
        if stack is None:
            stack = self._stack.names = []
        stack.append(name)

    def _pop(self) -> None:
        stack = getattr(self._stack, "names", None)
        if stack:
            stack.pop()

    def current(self) -> Optional[str]:
        stack = getattr(self._stack, "names", None)
        return stack[-1] if stack else None

    def depth(self) -> int:
        stack = getattr(self._stack, "names", None)
        return len(stack) if stack else 0

    # -- recording --------------------------------------------------------
    def record(self, name: str, seconds: float, counts: Optional[Dict[str, float]] = None) -> None:
        with self._lock:
            self._totals[name] = self._totals.get(name, 0.0) + seconds
            self._counts[name] = self._counts.get(name, 0) + 1
            if counts:
                sums = self._sums.setdefault(name, {})
                for k, v in counts.items():
                    sums[k] = sums.get(k, 0) + v

    def compute(self, reset: bool = False) -> Dict[str, float]:
        """Snapshot name → accumulated seconds; ``reset=True`` drains
        atomically (snapshot and clear under one lock acquisition)."""
        with self._lock:
            out = dict(self._totals)
            if reset:
                self._clear()
        return out

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def sums(self) -> Dict[str, Dict[str, float]]:
        """Snapshot name → {count name → sum over the spans recorded}."""
        with self._lock:
            return {name: dict(sums) for name, sums in self._sums.items()}

    def _clear(self) -> None:
        self._totals.clear()
        self._counts.clear()
        self._sums.clear()

    def reset(self) -> None:
        with self._lock:
            self._clear()

    def span(self, name: str, enabled: bool = True, annotate: bool = True, **counts: float) -> "Span":
        return Span(name, tracker=self, enabled=enabled, annotate=annotate, **counts)


# The process-wide tracker: the legacy `utils.timer` shim and every
# `Telemetry` facade instance share it, so old and new call sites drain into
# one registry.
GLOBAL_TRACKER = SpanTracker()


class Span:
    """Context manager: wall-clock accumulation + device-trace annotation.

    Reentrant across threads (each `with` creates independent local state via
    __enter__ returning a token would be nicer, but the historical `timer`
    API constructs one object per `with`, which we keep).

    ``counts`` (keyword arguments, numbers; `count()` for those known only
    inside) are work done inside the span; ``elapsed`` holds its seconds
    after exit (a disabled span still has it: it only stays out of the
    tracker and the capture), so a caller that keeps a total of its own
    feeds it from this one stopwatch.
    """

    def __init__(
        self,
        name: str,
        tracker: Optional[SpanTracker] = None,
        enabled: bool = True,
        annotate: bool = True,
        **counts: float,
    ) -> None:
        self.name = name
        self.tracker = tracker if tracker is not None else GLOBAL_TRACKER
        self.enabled = enabled
        self.annotate = annotate
        self.counts = counts
        self.elapsed = 0.0
        self._start: Optional[float] = None
        self._ann = None

    def __enter__(self) -> "Span":
        if self.enabled:
            self.tracker._push(self.name)
            if self.annotate:
                self._ann = _trace_annotation(self.name, self.counts)
                if self._ann is not None:
                    try:
                        self._ann.__enter__()
                    except Exception:
                        self._ann = None
        self._start = time.perf_counter()
        return self

    def count(self, **counts: float) -> None:
        """Counts known only once the work is done (packets taken, rows
        shipped): call inside the span, once per count name."""
        if self.enabled:
            self.counts.update(counts)
            if self._ann is not None:
                try:
                    self._ann.set_metadata(**counts)
                except Exception:
                    pass

    def __exit__(self, *exc) -> bool:
        if self._start is None:
            return False
        self.elapsed = time.perf_counter() - self._start
        self._start = None
        if self.enabled:
            if self._ann is not None:
                try:
                    self._ann.__exit__(*exc)
                except Exception:
                    pass
                self._ann = None
            self.tracker._pop()
            self.tracker.record(self.name, self.elapsed, self.counts)
        return False
