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
"""
from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

OPEN_MARK = "perfbench.window_open"
CLOSE_MARK = "perfbench.window_close"
SPAN_PREFIX = "Time/"
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
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    modules: List[Tuple[str, float, float]] = []
    ops: List[Tuple[str, float, float]] = []
    spans: List[Tuple[str, float, float]] = []
    device_planes = 0
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU:") or plane.name.startswith("/device:GPU:")
        is_host = plane.name.startswith("/host:CPU")
        if not (is_device or is_host):
            continue
        device_planes += int(is_device)
        for line in plane.lines:
            if is_device and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            target = modules if line.name == "XLA Modules" else ops
            for ev in line.events:
                name = ev.name
                if is_host:
                    if name.startswith(SPAN_PREFIX) or name in (OPEN_MARK, CLOSE_MARK):
                        spans.append((name, ev.start_ns, ev.start_ns + ev.duration_ns))
                else:
                    target.append((name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return {"modules": modules, "ops": ops, "spans": spans, "device_planes": max(device_planes, 1)}


def reduce_events(planes: Dict[str, Any]) -> Dict[str, Any]:
    spans = planes["spans"]
    opens = [s for n, s, _ in spans if n == OPEN_MARK]
    closes = [s for n, s, _ in spans if n == CLOSE_MARK]
    every = [t for _, s, e in planes["modules"] + planes["ops"] + spans for t in (s, e)]
    if not every:
        return {"window_s": 0.0, "busy_s": 0.0, "n_device_events": 0, "programs": {}, "top_ops": [],
                "idle_gaps": [], "idle_by_span": {}, "spans_s": {}, "marked": False}
    w0 = min(opens) if opens else min(every)
    w1 = max(closes) if closes else max(every)

    def clip(events):
        out = [(n, max(s, w0), min(e, w1)) for n, s, e in events if e > w0 and s < w1]
        return out

    modules, ops = clip(planes["modules"]), clip(planes["ops"])
    starts = np.array([s for _, s, _ in ops] + [s for _, s, _ in modules], float)
    ends = np.array([e for _, _, e in ops] + [e for _, _, e in modules], float)
    busy_ns, ms, me = union_length(starts, ends)
    n_dev = planes["device_planes"]

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
    named = sorted(((n, s, e) for n, s, e in spans if n.startswith(SPAN_PREFIX)), key=lambda x: x[1])
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
        "busy_s": busy_ns * 1e-9 / n_dev,
        "n_device_events": len(ops) + len(modules),
        "programs": programs,
        "top_ops": top_ops[:20],
        "idle_gaps": gap_list[:20],
        "idle_by_span": idle_by_span,
        "spans_s": spans_s,
        "marked": bool(opens and closes),
    }


def reduce_file(path: str) -> Dict[str, Any]:
    return reduce_events(read_planes(path))


def reduce_dir(trace_dir: str) -> Dict[str, Any]:
    files = find_xplanes(trace_dir)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(files[-1])
