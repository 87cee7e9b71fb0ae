"""XLA health counters: compiles, retraces (with shape attribution) and
device memory.

Independent mechanisms, each robust on its own:

* **global compile counters** — a `jax.monitoring` duration listener counts
  `/jax/core/compile/backend_compile_duration` events (one per backend
  compile, cache hits excluded) and accumulates compile seconds. Monotonic
  process-wide; the `Telemetry` facade snapshots at setup and reports deltas,
  so back-to-back runs in one process don't bleed into each other.
* **`RetraceDetector`** — wraps a python callable *before* `jax.jit`; the
  wrapper body only executes while JAX is tracing, so each execution is one
  (re)trace. It records the abstract shape/dtype signature of every trace
  and, on a retrace, diffs against the previous signature to say *which*
  argument changed shape — the attribution the BENCH rounds were missing.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, Dict, List, Optional

_lock = threading.Lock()
_counters: Dict[str, float] = {
    "compile_count": 0,
    "compile_seconds": 0.0,
    "jaxpr_trace_count": 0,
    # persistent-compilation-cache accounting (utils.enable_compilation_cache):
    # a hit means a backend compile was paid once on some earlier run/process
    "cache_hits": 0,
    "cache_misses": 0,
}
# per-function compile-seconds breakdown: tag → {count, seconds}. The tag is
# whatever the RetraceDetector last saw tracing on the *calling thread* —
# XLA compiles on the dispatching thread immediately after the jaxpr trace,
# so the thread-local trace tag names the function each compile belongs to.
# Compiles from never-instrumented functions land under "<untagged>".
_compile_breakdown: Dict[str, Dict[str, float]] = {}
_listener_installed = False
_tls = threading.local()

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
UNTAGGED = "<untagged>"


def _ensure_listener() -> None:
    """Register the monitoring listeners once per process (jax.monitoring
    has no unregister — the counters are monotonic by design)."""
    global _listener_installed
    with _lock:
        if _listener_installed:
            return
        _listener_installed = True
    import jax.monitoring as monitoring

    def _on_duration(name: str, secs: float, **_kw: Any) -> None:
        with _lock:
            if name == _COMPILE_EVENT:
                _counters["compile_count"] += 1
                _counters["compile_seconds"] += float(secs)
                tag = getattr(_tls, "tag", None) or UNTAGGED
                slot = _compile_breakdown.setdefault(tag, {"count": 0, "seconds": 0.0})
                slot["count"] += 1
                slot["seconds"] += float(secs)
            elif name == _TRACE_EVENT:
                _counters["jaxpr_trace_count"] += 1

    monitoring.register_event_duration_secs_listener(_on_duration)

    def _on_event(name: str, **_kw: Any) -> None:
        with _lock:
            if name == _CACHE_HIT_EVENT:
                _counters["cache_hits"] += 1
            elif name == _CACHE_MISS_EVENT:
                _counters["cache_misses"] += 1

    monitoring.register_event_listener(_on_event)


def compile_counters() -> Dict[str, float]:
    """Monotonic process-wide compile counters (installs the listener)."""
    _ensure_listener()
    with _lock:
        return dict(_counters)


def compile_breakdown() -> Dict[str, Dict[str, float]]:
    """Monotonic per-function compile-seconds breakdown (copy). Keys are
    RetraceDetector tags; compiles no instrumented trace preceded on the
    same thread fall under ``"<untagged>"``."""
    _ensure_listener()
    with _lock:
        return {tag: dict(slot) for tag, slot in _compile_breakdown.items()}


def device_memory_stats(device: Any = None) -> Dict[str, int]:
    """`device.memory_stats()` guarded: {} on backends without it (CPU)."""
    try:
        import jax

        dev = device if device is not None else jax.devices()[0]
        stats = dev.memory_stats() if hasattr(dev, "memory_stats") else None
        if not stats:
            return {}
        out: Dict[str, int] = {}
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit", "largest_alloc_size"):
            if key in stats:
                out[key] = int(stats[key])
        return out
    except Exception:
        return {}


def _signature(args: tuple, kwargs: dict) -> Dict[str, str]:
    """Flat leaf-path → 'shape dtype' signature of a call's abstract values."""
    import jax

    sig: Dict[str, str] = {}
    flat, _ = jax.tree_util.tree_flatten_with_path((args, kwargs))
    for path, leaf in flat:
        aval = getattr(leaf, "aval", None)
        shape = getattr(aval if aval is not None else leaf, "shape", None)
        dtype = getattr(aval if aval is not None else leaf, "dtype", None)
        if shape is None and dtype is None:
            desc = f"py:{type(leaf).__name__}"
        else:
            desc = f"{tuple(shape) if shape is not None else '?'} {dtype}"
        sig[jax.tree_util.keystr(path)] = desc
    return sig


class RetraceDetector:
    """Counts (re)traces of instrumented functions and attributes each
    retrace to the arguments whose shape/dtype changed."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._traces: Dict[str, List[Dict[str, str]]] = {}
        self._attribution: Dict[str, List[str]] = {}

    def wrap(self, fn: Callable, name: Optional[str] = None) -> Callable:
        """Wrap a python callable BEFORE jit; the wrapper body runs once per
        trace, never per call."""
        import functools

        tag = name or getattr(fn, "__name__", "jitted_fn")

        @functools.wraps(fn)
        def traced(*args, **kwargs):
            self._record(tag, args, kwargs)
            return fn(*args, **kwargs)

        return traced

    def _record(self, tag: str, args: tuple, kwargs: dict) -> None:
        # mark this thread as "tracing `tag`": the backend compile that
        # follows (same thread, before any other instrumented trace) gets
        # its seconds attributed to this tag by the duration listener
        _tls.tag = tag
        if getattr(_tls, "suppress_retraces", False):
            # a diagnostic re-trace (roofline `.lower()` of an already-jitted
            # fn): keep the compile attribution, skip the retrace ledger so
            # it never reads as a shape-instability signal
            return
        try:
            sig = _signature(args, kwargs)
        except Exception:
            sig = {}
        with self._lock:
            history = self._traces.setdefault(tag, [])
            if history:
                prev = history[-1]
                changed = [
                    f"{path}: {prev.get(path, '<new>')} -> {desc}"
                    for path, desc in sig.items()
                    if prev.get(path) != desc
                ]
                changed += [
                    f"{path}: {desc} -> <removed>"
                    for path, desc in prev.items()
                    if path not in sig
                ]
                self._attribution.setdefault(tag, []).append(
                    f"retrace #{len(history)} of '{tag}': "
                    + ("; ".join(changed) if changed else "no leaf shape change (weak-type/static arg?)")
                )
            history.append(sig)

    def trace_count(self, tag: Optional[str] = None) -> int:
        with self._lock:
            if tag is not None:
                return len(self._traces.get(tag, []))
            return sum(len(v) for v in self._traces.values())

    def retrace_count(self, tag: Optional[str] = None) -> int:
        with self._lock:
            if tag is not None:
                return max(0, len(self._traces.get(tag, [])) - 1)
            return sum(max(0, len(v) - 1) for v in self._traces.values())

    def attribution(self, tag: Optional[str] = None) -> List[str]:
        with self._lock:
            if tag is not None:
                return list(self._attribution.get(tag, []))
            out: List[str] = []
            for msgs in self._attribution.values():
                out.extend(msgs)
            return out

    def reset(self) -> None:
        with self._lock:
            self._traces.clear()
            self._attribution.clear()


# Facade default: loops (and tests) that don't build their own detector share
# this one; the facade reports deltas against its setup-time snapshot.
RETRACE_DETECTOR = RetraceDetector()


def instrument(fn: Callable, name: Optional[str] = None) -> Callable:
    """Convenience: wrap `fn` with the process-default RetraceDetector."""
    return RETRACE_DETECTOR.wrap(fn, name)


@contextlib.contextmanager
def suppress_retrace_accounting():
    """Deliberate diagnostic traces (roofline `.lower()` of an already-jitted
    fn) inside this context keep their compile-seconds attribution but are
    not entered in the retrace ledger — they are not shape instability."""
    _tls.suppress_retraces = True
    try:
        yield
    finally:
        _tls.suppress_retraces = False
