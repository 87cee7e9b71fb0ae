"""For the readers that name a host thread or a part of the train step: a
second look at the capture `trace_reduce.read_planes` parsed (once).

Kept here: for every host event whose name starts with ``Time/``, ``Wait/``
or ``Player/`` the line (thread) it lies on and its stats (the counts a
program span carries: ``grad_steps``, ``burst``, ``version``, ``bytes``);
and for every `XLA Ops` event that starts inside an execution of the step's
programs (the adapter's `step_programs`; `jit_train` for DreamerV3) the part
of the step it belongs to: one of the adapter's `step_parts`, the
`jax.named_scope` names of that step, which `run.py` hands to `Capture`.
No algorithm's scopes are listed here. PERF.md (section 3) says where a v5e
capture keeps the HLO `op_name` (the stat `tf_op` of the event's metadata,
which `ProfileData` does not hand out: `read_tf_ops` below) and how a fusion
over two parts is named.

`run.py` puts the `Capture` into the readers' `ctx` under ``"capture"``
(``None`` where there is no capture, so that every reader returns ``None``
too) beside ``"trace_dir"``. Times per gradient step divide by the executions
of the step's programs that lie wholly inside the window, times the gradient
steps one call takes: the train calls that RETURNED in the window are one
fewer than the executions in some windows (PERF.md, Findings of PR 31).
"""
from __future__ import annotations

import re
from typing import Any, Collection, Dict, List, Optional, Sequence, Tuple

import numpy as np

from perfbench import trace_reduce as tr

_WRAPPED = re.compile(r"[A-Za-z_]+\((.*)\)")  # jvp(..), transpose(..), jit(..)


def _legacy_parts() -> Tuple[str, ...]:
    # `tests/test_train_scopes.py` (outside the benchmark's own directories, so no benchmark PR may edit it) still
    # imports `PARTS` from here and calls `part_of(op_name)` with no parts; `overrides.py` keeps that test's lazy names
    from perfbench import overrides

    return overrides.STEP_PARTS


def __getattr__(name: str):
    if name == "PARTS":
        return _legacy_parts()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def part_of(op_name: str, parts: Optional[Collection[str]] = None) -> Optional[str]:
    """The innermost of `parts` (an adapter's `step_parts`) that is a whole
    `/`-separated component of an HLO `op_name`; autodiff wraps a component
    (`transpose(jvp(<part>))`), so the wrappers are peeled first. None where
    no component is a part."""
    if parts is None:
        parts = _legacy_parts()
    found = None
    for comp in op_name.split("/"):
        while True:
            m = _WRAPPED.fullmatch(comp)
            if m is None:
                break
            comp = m.group(1)
        if comp in parts:
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


def read_tf_ops(path: str, prefer: Tuple[str, ...] = ("jit(train)",)) -> Dict[str, str]:
    """HLO text of an instruction (an `XLA Ops` event's name) -> its `tf_op`
    (`<op_name>:<op type>`), over the device planes of one capture. Where two
    programs hold the same text, the entry of `prefer` (the step's program,
    as `jit(train)`) is the one kept."""
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
                if tf_op and (text not in out or tf_op.startswith(prefer)):
                    out[text] = tf_op
    return out


class Capture:
    """The window, the program's host spans by thread, and the ops of the step's programs by part."""

    def __init__(self, planes: Dict[str, Any], step_programs: Sequence[str], step_parts: Sequence[str]):
        self.step_programs = tuple(step_programs)
        self.step_parts = tuple(step_parts)  # the step's `jax.named_scope` names, as the cell's adapter has them
        parts = frozenset(self.step_parts)
        self.host = [ev for ev in planes["host"] if ev[0] not in (tr.OPEN_MARK, tr.CLOSE_MARK)]  # name, thread, start, end, stats
        self.w0, self.w1, _ = tr.window_of(planes)
        self.window_s = (self.w1 - self.w0) * 1e-9

        # device busy intervals in the window, as trace_reduce takes them: those of the fullest plane
        busy = tr.busy_by_plane(planes, self.w0, self.w1)
        full = tr.fullest(busy)
        _, self.busy_s, self.busy_e = busy[full] if full is not None else (0.0, np.zeros(0), np.zeros(0))
        dev = planes["devices"].get(full, {"modules": [], "ops": []})

        # ops of the step's programs, by part, over the executions that lie wholly inside
        # the window; wrappers only hold the ops of a body
        train_runs = sorted((s, e) for n, s, e in dev["modules"] if tr.program_name(n) in self.step_programs)
        run_s = np.asarray([a for a, _ in train_runs], float)
        run_e = np.asarray([b for _, b in train_runs], float)
        whole = (run_s >= self.w0) & (run_e <= self.w1)
        self.step_executions = int(whole.sum())
        self.step_seconds = float(np.sum((run_e - run_s)[whole])) * 1e-9
        self.train_ops: List[Tuple[Optional[str], str, float]] = []  # part, op's short name, seconds
        part_by_name: Dict[str, Optional[str]] = {}
        prefer = tuple("jit(" + p[len("jit_"):] + ")" for p in self.step_programs if p.startswith("jit_"))
        tf_ops = read_tf_ops(planes["path"], prefer) if train_runs and planes.get("path") else {}
        for name, s0, e0 in dev["ops"]:
            if not len(run_s):
                break
            j = int(np.searchsorted(run_s, s0, side="right")) - 1
            if j < 0 or s0 >= run_e[j] or not whole[j]:
                continue
            short = tr.short_name(name)
            if short.split(".", 1)[0] in tr.WRAPPERS:
                continue
            if name not in part_by_name:
                part_by_name[name] = part_of(tf_ops.get(name, ""), parts)
            self.train_ops.append((part_by_name[name], short, (e0 - s0) * 1e-9))
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


def steps_per_call(ctx: Dict[str, Any]) -> float:
    """Gradient steps one train call takes in this window (1 in both accepted cells)."""
    win = ctx["window"]
    return win["grad_steps"] / win["train_calls"] if win.get("train_calls") else 1.0


# -- what the metric files call ------------------------------------------------
def _idle_under(cap: Capture, intervals: List[Tuple[float, float]]) -> Tuple[float, float]:
    """(the window's idle nanoseconds, those of them that the union of `intervals` covers), by interval intersection."""
    gs, ge = cap.idle_intervals()
    idle = float(np.sum(ge - gs))
    clipped = [(max(s, cap.w0), min(e, cap.w1)) for s, e in intervals]
    spanned, ms, me = tr.union_length(np.asarray([a for a, _ in clipped], float), np.asarray([b for _, b in clipped], float))
    either, _, _ = tr.union_length(np.concatenate([gs, ms]), np.concatenate([ge, me]))
    return idle, idle + spanned - either  # |idle and spanned| = |idle| + |spanned| - |idle or spanned|


def span_share_pct(ctx: Dict[str, Any], name: str) -> Optional[float]:
    cap = ctx.get("capture")
    if cap is None or cap.window_s <= 0:
        return None
    sec = cap.span_seconds(name)
    return None if sec is None else 100.0 * sec / cap.window_s


def span_idle_share_pct(ctx: Dict[str, Any], name: str) -> Optional[float]:
    """Share of the window in which a span of that name is open AND the device
    is idle, by interval intersection: who waits while nothing runs."""
    cap = ctx.get("capture")
    if cap is None or cap.window_s <= 0 or cap.span_seconds(name) is None:
        return None
    _, covered = _idle_under(cap, [(s, e) for _, s, e, _ in cap.spans(name)])
    return 100.0 * covered * 1e-9 / cap.window_s


def span_median_ms(ctx: Dict[str, Any], name: str) -> Optional[float]:
    cap = ctx.get("capture")
    if cap is None:
        return None
    durs = [(e - s) * 1e-6 for _, s, e, _ in cap.spans(name) if s >= cap.w0 and e <= cap.w1]
    return float(np.median(durs)) if durs else None


def spans_ms_per_grad_step(ctx: Dict[str, Any], names: Tuple[str, ...]) -> Optional[float]:
    cap, grad_steps = ctx.get("capture"), ctx["window"]["grad_steps"]
    if cap is None or grad_steps <= 0:
        return None
    found = [cap.span_seconds(n) for n in names]
    if all(f is None for f in found):
        return None
    return 1e3 * sum(f for f in found if f is not None) / grad_steps


def step_ms(ctx: Dict[str, Any]) -> Optional[float]:
    """Device time of the step's programs per gradient step, over their whole executions in the window."""
    cap = ctx.get("capture")
    if cap is None or cap.step_executions <= 0:
        return None
    return 1e3 * cap.step_seconds / (cap.step_executions * steps_per_call(ctx))


def part_ms(ctx: Dict[str, Any], part: str) -> Optional[float]:
    """Device time of the step's ops under that scope, per gradient step;
    None where the step has no such part (another adapter's), where the
    program has no scopes (the parent), or where there is no capture."""
    cap = ctx.get("capture")
    if cap is None or part not in cap.step_parts or not cap.scoped or cap.step_executions <= 0:
        return None
    return 1e3 * cap.part_seconds().get(part, 0.0) / (cap.step_executions * steps_per_call(ctx))


def unscoped_pct(ctx: Dict[str, Any]) -> Optional[float]:
    """Share of the step's summed op time under none of the capture's own
    `step_parts`; None for a step that has none, or whose program emits none."""
    cap = ctx.get("capture")
    if cap is None or not cap.scoped:
        return None
    by_part = cap.part_seconds()
    total = sum(by_part.values())
    return 100.0 * by_part.get(None, 0.0) / total if total > 0 else None


def idle_unattributed_pct(ctx: Dict[str, Any]) -> Optional[float]:
    """Share of the window's idle time that no `Time/`, `Wait/` or `Player/`
    span on any thread covers, by interval intersection. None for a program
    without the layer-boundary spans: its two old ones would read a share too,
    but of another question (the parent of the PR that brought the rest)."""
    cap = ctx.get("capture")
    if cap is None or not cap.instrumented:
        return None
    idle, covered = _idle_under(cap, [(s, e) for _, _, s, e, _ in cap.host if e > cap.w0 and s < cap.w1])
    return 100.0 * (1.0 - covered / idle) if idle > 0 else None
