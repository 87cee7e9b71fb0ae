"""What `trace_reduce` throws away, for the readers that name a host thread or
a part of `jit(train)`: read from the same `*.xplane.pb` with
`jax.profiler.ProfileData`, nothing else.

Kept here: for every host event whose name starts with ``Time/``, ``Wait/``
or ``Player/`` the line (thread) it lies on and its stats (the counts a
program span carries: ``grad_steps``, ``burst``, ``version``, ``bytes``);
and for every `XLA Ops` event that starts inside an execution of `jit_train`
the part of the step it belongs to. PERF.md (section 3) says where a v5e
capture keeps the HLO `op_name` (the stat `tf_op` of the event's metadata,
which `ProfileData` does not hand out: `read_tf_ops` below) and how a fusion
over two parts is named.

`run.py` gives the readers no `trace_dir`; it calls them with the run's
temporary directory as the working directory and the capture under
``./trace``. `load()` looks there, once per process, and returns ``None``
where there is no capture, so that every reader returns ``None`` too.
"""
from __future__ import annotations

import os
import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from perfbench import trace_reduce as tr

HOST_PREFIXES = ("Time/", "Wait/", "Player/")
TRAIN_PROGRAM = "jit_train"
# the `jax.named_scope` names of `make_train_fn`'s `one_step`
PARTS = ("wm_encoder", "wm_rssm", "wm_decoder", "wm_heads", "imagination", "actor", "critic", "optimizer")
_PARTS = frozenset(PARTS)
_WRAPPED = re.compile(r"[A-Za-z_]+\((.*)\)")  # jvp(..), transpose(..), jit(..)

_CACHE: Dict[str, Optional["Capture"]] = {}


def part_of(op_name: str) -> Optional[str]:
    """The innermost of PARTS that is a whole `/`-separated component of an
    HLO `op_name`; autodiff wraps a component (`transpose(jvp(wm_rssm))`), so
    the wrappers are peeled first. None where no component is a part."""
    found = None
    for comp in op_name.split("/"):
        while True:
            m = _WRAPPED.fullmatch(comp)
            if m is None:
                break
            comp = m.group(1)
        if comp in _PARTS:
            found = comp
    return found


# -- the one thing ProfileData does not show -------------------------------------
# A v5e capture keeps an op's `op_name` in the stat `tf_op` of the event's
# METADATA (one entry per HLO instruction, shared by all its events), and
# `ProfileData` hands out an event's own stats only (`device_offset_ps`,
# `device_duration_ps`). So the metadata table of the device planes is read
# from the file's protobuf wire format here: XSpace.planes = 1; XPlane.name = 2,
# .event_metadata = 4, .stat_metadata = 5 (maps: key = 1, value = 2);
# XEventMetadata.name = 2, .stats = 5; XStatMetadata.id = 1, .name = 2;
# XStat.metadata_id = 1, .str_value = 5, .ref_value = 7. The lines, which are
# nearly all of the file, are skipped by their length.
def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf: memoryview):
    """(field number, wire type, value) of one message: ints for varints, views for length-delimited."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
            yield num, wt, val
        elif wt == 2:
            size, i = _varint(buf, i)
            yield num, wt, buf[i:i + size]
            i += size
        elif wt == 1:
            i += 8
        elif wt == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wt} in an xplane file")


def _sub(buf: memoryview, field: int):
    """The length-delimited values of one field of a message."""
    return (val for num, wt, val in _fields(buf) if num == field and wt == 2)


def _text(view: memoryview) -> str:
    return bytes(view).decode("utf-8", "replace")


def read_tf_ops(path: str) -> Dict[str, str]:
    """HLO text of an instruction (an `XLA Ops` event's name) -> its `tf_op`
    (`<op_name>:<op type>`), over the device planes of one capture. Where two
    programs hold the same text, `jit(train)`'s entry is the one kept."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out: Dict[str, str] = {}
    for plane in _sub(data, 1):
        if not next((_text(v) for v in _sub(plane, 2)), "").startswith(("/device:TPU:", "/device:GPU:")):
            continue
        stat_names: Dict[int, str] = {}
        for entry in _sub(plane, 5):
            for meta in _sub(entry, 2):
                f = {num: val for num, _, val in _fields(meta)}
                stat_names[f.get(1, 0)] = _text(f[2]) if 2 in f else ""
        tf_op_ids = {sid for sid, sname in stat_names.items() if sname == "tf_op"}
        for entry in _sub(plane, 4):
            for meta in _sub(entry, 2):
                text, tf_op = "", ""
                for num, wt, val in _fields(meta):
                    if num == 2 and wt == 2:
                        text = _text(val)
                    elif num == 5 and wt == 2:
                        stat = {snum: sval for snum, _, sval in _fields(val)}
                        if stat.get(1) in tf_op_ids:
                            tf_op = _text(stat[5]) if 5 in stat else stat_names.get(stat.get(7), "")
                if tf_op and (text not in out or tf_op.startswith("jit(train)")):
                    out[text] = tf_op
    return out


class Capture:
    """The window, the program's host spans by thread, and the ops of `jit_train` by part."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        self.host: List[Tuple[str, str, float, float, Dict[str, Any]]] = []  # name, thread, start, end, stats
        marks: Dict[str, List[float]] = {tr.OPEN_MARK: [], tr.CLOSE_MARK: []}
        dev_s: List[float] = []
        dev_e: List[float] = []
        train_runs: List[Tuple[float, float]] = []
        ops: List[Tuple[str, float, float]] = []
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
                        if name in marks:
                            marks[name].append(ev.start_ns)
                        elif name.startswith(HOST_PREFIXES):
                            self.host.append((name, thread, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats)))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                        dev_s.append(s)
                        dev_e.append(e)
                        if tr.program_name(ev.name) == TRAIN_PROGRAM:
                            train_runs.append((s, e))
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                        dev_s.append(s)
                        dev_e.append(e)
                        ops.append((ev.name, s, e))
        every = dev_s + dev_e + [t for _, _, s, e, _ in self.host for t in (s, e)]
        self.w0 = min(marks[tr.OPEN_MARK]) if marks[tr.OPEN_MARK] else (min(every) if every else 0.0)
        self.w1 = max(marks[tr.CLOSE_MARK]) if marks[tr.CLOSE_MARK] else (max(every) if every else 0.0)
        self.window_s = (self.w1 - self.w0) * 1e-9

        # device busy intervals in the window, as trace_reduce takes them
        s = np.clip(np.asarray(dev_s, float), self.w0, self.w1)
        e = np.clip(np.asarray(dev_e, float), self.w0, self.w1)
        keep = e > s
        _, self.busy_s, self.busy_e = tr.union_length(s[keep], e[keep])

        # ops of jit_train inside the window, by part; wrappers only hold the ops of a body
        train_runs.sort()
        run_s = np.asarray([a for a, _ in train_runs], float)
        run_e = np.asarray([b for _, b in train_runs], float)
        self.train_ops: List[Tuple[Optional[str], str, float]] = []  # part, op's short name, seconds in the window
        part_by_name: Dict[str, Optional[str]] = {}
        tf_ops = read_tf_ops(path) if train_runs else {}
        for name, s0, e0 in ops:
            if not len(run_s) or e0 <= self.w0 or s0 >= self.w1:
                continue
            j = int(np.searchsorted(run_s, s0, side="right")) - 1
            if j < 0 or s0 >= run_e[j]:
                continue
            short = tr.short_name(name)
            if short.split(".", 1)[0] in tr.WRAPPERS:
                continue
            if name not in part_by_name:
                part_by_name[name] = part_of(tf_ops.get(name, ""))
            self.train_ops.append((part_by_name[name], short, (min(e0, self.w1) - max(s0, self.w0)) * 1e-9))
        self.scoped = any(p is not None for p, _, _ in self.train_ops)
        # a program that has the layer-boundary spans shows some in any capture; one of
        # them that did not occur in the window then reads 0, not "nothing to read"
        self.instrumented = any(n.startswith(("Wait/", "Player/")) or n == "Time/param_refresh" for n, *_ in self.host)

    # -- host spans -------------------------------------------------------
    def spans(self, name: str) -> List[Tuple[str, float, float, Dict[str, Any]]]:
        """(thread, start, end, stats) of the spans of that name that overlap the window."""
        return [(th, s, e, st) for n, th, s, e, st in self.host if n == name and e > self.w0 and s < self.w1]

    def span_seconds(self, name: str) -> Optional[float]:
        """Seconds of the window inside spans of that name; None where the
        program has no such span (the parent of the PR that brought them)."""
        found = self.spans(name)
        if not found:
            return 0.0 if self.instrumented else None
        return sum(min(e, self.w1) - max(s, self.w0) for _, s, e, _ in found) * 1e-9

    def learner_thread(self) -> Optional[str]:
        """The thread that carries `Time/train_time`."""
        for n, th, _, _, _ in self.host:
            if n == "Time/train_time":
                return th
        return None

    # -- device -----------------------------------------------------------
    def idle_intervals(self) -> Tuple[np.ndarray, np.ndarray]:
        starts = np.concatenate([[self.w0], self.busy_e])
        ends = np.concatenate([self.busy_s, [self.w1]])
        keep = ends > starts
        return starts[keep], ends[keep]

    def part_seconds(self) -> Dict[Optional[str], float]:
        out: Dict[Optional[str], float] = {}
        for part, _, sec in self.train_ops:
            out[part] = out.get(part, 0.0) + sec
        return out


def load() -> Optional[Capture]:
    """The capture under ./trace, parsed once per process; None where there is none."""
    trace_dir = os.path.join(os.getcwd(), "trace")
    if trace_dir not in _CACHE:
        files = tr.find_xplanes(trace_dir)
        _CACHE[trace_dir] = Capture(files[-1]) if files else None
    return _CACHE[trace_dir]


# -- what the metric files call ------------------------------------------------
def span_share_pct(name: str) -> Optional[float]:
    cap = load()
    if cap is None or cap.window_s <= 0:
        return None
    sec = cap.span_seconds(name)
    return None if sec is None else 100.0 * sec / cap.window_s


def span_median_ms(name: str) -> Optional[float]:
    cap = load()
    if cap is None:
        return None
    durs = [(e - s) * 1e-6 for _, s, e, _ in cap.spans(name) if s >= cap.w0 and e <= cap.w1]
    return float(np.median(durs)) if durs else None


def spans_ms_per_grad_step(names: Tuple[str, ...], grad_steps: int) -> Optional[float]:
    cap = load()
    if cap is None or grad_steps <= 0:
        return None
    found = [cap.span_seconds(n) for n in names]
    if all(f is None for f in found):
        return None
    return 1e3 * sum(f for f in found if f is not None) / grad_steps


def part_ms(part: str, grad_steps: int) -> Optional[float]:
    """Device time of `jit_train`'s ops under that scope, per gradient step;
    None where the program has no scopes (the parent) or no capture."""
    cap = load()
    if cap is None or not cap.scoped or grad_steps <= 0:
        return None
    return 1e3 * cap.part_seconds().get(part, 0.0) / grad_steps


def unscoped_pct() -> Optional[float]:
    cap = load()
    if cap is None or not cap.scoped:
        return None
    by_part = cap.part_seconds()
    total = sum(by_part.values())
    return 100.0 * by_part.get(None, 0.0) / total if total > 0 else None


def idle_unattributed_pct() -> Optional[float]:
    """Share of the window's idle time that no `Time/`, `Wait/` or `Player/`
    span on any thread covers, by interval intersection. None for a program
    without the layer-boundary spans: its two old ones would read a share too,
    but of another question (the parent of the PR that brought the rest)."""
    cap = load()
    if cap is None or not cap.instrumented:
        return None
    gs, ge = cap.idle_intervals()
    idle = float(np.sum(ge - gs))
    if idle <= 0:
        return None
    inside = [(max(s, cap.w0), min(e, cap.w1)) for _, _, s, e, _ in cap.host if e > cap.w0 and s < cap.w1]
    spanned, ms, me = tr.union_length(np.asarray([a for a, _ in inside], float), np.asarray([b for _, b in inside], float))
    either, _, _ = tr.union_length(np.concatenate([gs, ms]), np.concatenate([ge, me]))
    covered = idle + spanned - either  # |idle and spanned| = |idle| + |spanned| - |idle or spanned|
    return 100.0 * (1.0 - covered / idle)
