"""From a `jax.profiler` capture (`*.xplane.pb`) to the numbers the per-layer
readers use. Read with `jax.profiler.ProfileData`, nothing else.

What a v5e capture holds (looked at by hand, PERF.md): a plane
``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per execution of
a compiled program, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (every HLO
op; a ``while`` wraps the ops of its body, so intervals nest) and ``Async XLA
Ops``; and a plane ``/host:CPU`` with one line per thread, on which the
program's ``TraceAnnotation`` spans (``Time/train_time``,
``Time/env_interaction_time``) and the harness's two window marks lie. All
starts are nanoseconds on one clock.

The window is what lies between the marks ``perfbench.window_open`` and
``perfbench.window_close``; without marks it is the whole capture.

A capture is parsed ONCE (`read_planes`); `reduce_events` here and
`span_reduce.Capture` both read that. Busy and idle time are taken per device
plane. ``busy_s`` is the mean over the planes (what the result line's `device`
reports); whatever belongs to one device (``busy_fullest_s``, the idle gaps,
the programs' and ops' times) is that of the fullest plane, the device that
was busy longest, which with one chip is the only one.
"""
from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

OPEN_MARK = "perfbench.window_open"
CLOSE_MARK = "perfbench.window_close"
SPAN_PREFIX = "Time/"
HOST_PREFIXES = ("Time/", "Wait/", "Player/")  # what is kept of the host plane, with thread and stats
WRAPPERS = ("while", "conditional", "call")  # ops that only wrap the ops of a body


def find_xplanes(trace_dir: str) -> List[str]:
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))


def union_length(starts: np.ndarray, ends: np.ndarray) -> Tuple[float, np.ndarray, np.ndarray]:
    """Length of the union of intervals, and the merged intervals."""
    if len(starts) == 0:
        return 0.0, np.zeros(0), np.zeros(0)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    ms = s[new]
    idx = np.flatnonzero(new)
    me = np.maximum.reduceat(e, idx)
    return float(np.sum(me - ms)), ms, me


def short_name(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%").strip()


def program_name(name: str) -> str:
    return name.split("(", 1)[0]


def read_planes(path: str) -> Dict[str, Any]:
    """The one parse of a capture: per device plane the `XLA Modules` and
    `XLA Ops` events as (name, start, end), and of the host plane every
    `Time/`, `Wait/` and `Player/` span and the window marks as (name, thread,
    start, end, stats)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Tuple[str, float, float]]]] = {}
    host: List[Tuple[str, str, float, float, Dict[str, Any]]] = []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU:") or plane.name.startswith("/device:GPU:")
        is_host = plane.name.startswith("/host:CPU")
        if not (is_device or is_host):
            continue
        for i, line in enumerate(plane.lines):
            if is_host:
                thread = f"{line.name}#{i}"  # thread names repeat ("python3"): the line's place tells them apart
                for ev in line.events:
                    name = ev.name
                    if name.startswith(HOST_PREFIXES):
                        host.append((name, thread, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats)))
                    elif name in (OPEN_MARK, CLOSE_MARK):
                        host.append((name, thread, ev.start_ns, ev.start_ns + ev.duration_ns, {}))
            elif line.name in ("XLA Modules", "XLA Ops"):
                dev = devices.setdefault(plane.name, {"modules": [], "ops": []})
                target = dev["modules"] if line.name == "XLA Modules" else dev["ops"]
                for ev in line.events:
                    target.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return {"devices": devices, "host": host, "path": path}


def window_of(planes: Dict[str, Any]) -> Tuple[float, float, bool]:
    """(start, end, whether both marks were there) of the measured window."""
    opens = [s for n, _, s, _, _ in planes["host"] if n == OPEN_MARK]
    closes = [s for n, _, s, _, _ in planes["host"] if n == CLOSE_MARK]
    every = [t for dev in planes["devices"].values() for _, s, e in dev["modules"] + dev["ops"] for t in (s, e)]
    every += [t for n, _, s, e, _ in planes["host"] if n not in (OPEN_MARK, CLOSE_MARK) for t in (s, e)]
    if not every and not (opens and closes):
        return 0.0, 0.0, False
    return (min(opens) if opens else min(every)), (max(closes) if closes else max(every)), bool(opens and closes)


def busy_by_plane(planes: Dict[str, Any], w0: float, w1: float) -> Dict[str, Tuple[float, np.ndarray, np.ndarray]]:
    """Per device plane: nanoseconds in which an op or a program ran inside the window, and the merged intervals."""
    out = {}
    for name, dev in planes["devices"].items():
        events = [(s, e) for _, s, e in dev["ops"] + dev["modules"] if e > w0 and s < w1]
        starts = np.clip(np.array([s for s, _ in events], float), w0, w1)
        ends = np.clip(np.array([e for _, e in events], float), w0, w1)
        out[name] = union_length(starts, ends)
    return out


def fullest(busy: Dict[str, Tuple[float, np.ndarray, np.ndarray]]) -> Optional[str]:
    """The plane that was busy longest (the first of equals, by name)."""
    return max(sorted(busy), key=lambda name: busy[name][0]) if busy else None


def reduce_events(planes: Dict[str, Any]) -> Dict[str, Any]:
    spans = [(n, s, e) for n, _, s, e, _ in planes["host"] if n.startswith(SPAN_PREFIX)]
    w0, w1, marked = window_of(planes)
    busy = busy_by_plane(planes, w0, w1)
    full = fullest(busy)
    if w1 <= w0 or (full is None and not spans):
        return {"window_s": 0.0, "busy_s": 0.0, "busy_fullest_s": 0.0, "busy_by_plane_s": {}, "n_device_events": 0, "programs": {},
                "top_ops": [], "idle_gaps": [], "idle_by_span": {}, "spans_s": {}, "marked": False}

    def clip(events):
        return [(n, max(s, w0), min(e, w1)) for n, s, e in events if e > w0 and s < w1]

    dev = planes["devices"].get(full, {"modules": [], "ops": []})
    modules, ops = clip(dev["modules"]), clip(dev["ops"])
    busy_ns, ms, me = busy[full] if full is not None else (0.0, np.zeros(0), np.zeros(0))

    programs: Dict[str, Dict[str, float]] = {}
    for n, s, e in modules:
        p = programs.setdefault(program_name(n), {"seconds": 0.0, "executions": 0})
        p["seconds"] += (e - s) * 1e-9
        p["executions"] += 1

    by_op: Dict[str, float] = {}
    for n, s, e in ops:
        sn = short_name(n)
        if sn.split(".", 1)[0] in WRAPPERS:
            continue
        by_op[sn] = by_op.get(sn, 0.0) + (e - s) * 1e-9
    top_ops = sorted(([k, v] for k, v in by_op.items()), key=lambda kv: -kv[1])

    # idle gaps inside the window, named by the program's host span over their middle
    named = sorted(spans, key=lambda x: x[1])
    gaps: List[Tuple[float, float]] = []
    edges_s = np.concatenate([[w0], me]) if len(me) else np.array([w0])
    edges_e = np.concatenate([ms, [w1]]) if len(ms) else np.array([w1])
    for a, b in zip(edges_s, edges_e):
        if b > a:
            gaps.append((a, b))

    def span_over(t: float) -> str:
        best: Optional[Tuple[str, float]] = None
        for n, s, e in named:
            if s > t:
                break
            if e >= t and (best is None or e - s < best[1]):
                best = (n, e - s)
        return best[0] if best else "unattributed"

    idle_by_span: Dict[str, float] = {}
    gap_list = []
    for a, b in gaps:
        name = span_over(0.5 * (a + b))
        idle_by_span[name] = idle_by_span.get(name, 0.0) + (b - a) * 1e-9
        gap_list.append([name, (b - a) * 1e-9])
    gap_list.sort(key=lambda kv: -kv[1])

    spans_s: Dict[str, float] = {}
    for n, s, e in named:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            spans_s[n] = spans_s.get(n, 0.0) + (e - s) * 1e-9

    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": float(np.mean([b[0] for b in busy.values()])) * 1e-9 if busy else 0.0,
        "busy_fullest_s": busy_ns * 1e-9,
        "busy_by_plane_s": {name: b[0] * 1e-9 for name, b in busy.items()},
        "n_device_events": sum(1 for d in planes["devices"].values() for _, s, e in d["ops"] + d["modules"] if e > w0 and s < w1),
        "programs": programs,
        "top_ops": top_ops[:20],
        "idle_gaps": gap_list[:20],
        "idle_by_span": idle_by_span,
        "spans_s": spans_s,
        "marked": marked,
    }


def reduce_file(path: str) -> Dict[str, Any]:
    return reduce_events(read_planes(path))


def read_dir(trace_dir: str) -> Dict[str, Any]:
    """The parsed capture under a directory `jax.profiler` wrote."""
    files = find_xplanes(trace_dir)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return read_planes(files[-1])
