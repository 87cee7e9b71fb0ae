"""Cut a capture down to a fixture for `span_reduce`, keeping what `trim_trace.py` drops:

    python3 perfbench/trim_scopes.py <adapter> <in.xplane.pb> <out.xplane.pb> <out.json> [seconds] [max_ops]

Keeps, from the first window mark on and for `seconds`: the device planes'
`XLA Modules` events, every k-th event of their `XLA Ops` line (k so that about
`max_ops` stay: a sample over the whole of a train step, so that every part of
it is there), each kept op's `tf_op` (the stat of its metadata that holds the
HLO `op_name`), and the host plane's `Time/`, `Wait/` and `Player/` spans with
their stats (the counts) and the window marks. Then reads the cut file with
every per-layer reader that `span_reduce` serves and writes the numbers beside
it, for the test that reads it again. `<adapter>` names the file under
`perfbench/adapters/` whose `step_programs` and `step_parts` the capture is
read by. Needs TensorFlow's xplane protobuf bindings, as `trim_trace.py` does.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
NAME_CHARS, TF_OP_CHARS = 100, 110  # the instruction's own name and the scope components come first in both


def readings(path: str, adapter) -> dict:
    """Every metric of BENCHMARK.json whose file reads through `span_reduce`, on one capture file of a cell of that adapter."""
    from perfbench import span_reduce, trace_reduce
    from perfbench.run import load_json, metric_reader

    planes = trace_reduce.read_planes(path)
    reduced = trace_reduce.reduce_events(planes)
    step_programs = adapter.step_programs
    cap = span_reduce.Capture(planes, step_programs, adapter.step_parts)
    executions = sum(reduced["programs"].get(p, {}).get("executions", 0) for p in step_programs)
    # one gradient step per execution in both cells
    ctx = {"window": {"grad_steps": executions, "train_calls": executions}, "capture": cap, "trace": reduced}
    out = {}
    for m in load_json(ROOT, "BENCHMARK.json")["per_layer"]:
        with open(os.path.join(ROOT, "perfbench", "metrics", m["name"] + ".py")) as f:
            if "span_reduce" in f.read():
                value = metric_reader(m["name"])(ctx)
                if value is not None:  # as `run.py` has it: a reader that finds nothing to read is left out
                    out[m["name"]] = value
    by_part = cap.part_seconds()
    return {"grad_steps": executions, "whole_executions": cap.step_executions, "window_s": cap.window_s, "metrics": out,
            "part_seconds": {str(k): v for k, v in by_part.items()}}


def main(argv) -> int:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from perfbench import adapters
    from perfbench import trace_reduce as tr

    adapter = adapters.load(argv[0])
    src, dst, dst_json = argv[1:4]
    seconds = float(argv[4]) if len(argv) > 4 else 0.45
    max_ops = int(argv[5]) if len(argv) > 5 else 1500
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())

    def start_ns(line, ev):
        return line.timestamp_ns + ev.offset_ps / 1000.0

    t_open = None
    for plane in space.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if plane.event_metadata[ev.metadata_id].name == tr.OPEN_MARK:
                        t_open = start_ns(line, ev)
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        host = plane.name.startswith("/host:CPU")
        if not (device or host):
            continue
        p = out.planes.add()
        p.id, p.name = plane.id, plane.name
        stat_ids = {m.name: sid for sid, m in plane.stat_metadata.items()}

        def keep_stat(src_stat, dst_stats, chars=None):
            d = dst_stats.add()
            d.CopyFrom(src_stat)
            if chars is not None and d.WhichOneof("value") == "str_value":
                d.str_value = d.str_value[:chars]
            for sid in (d.metadata_id, d.ref_value if d.WhichOneof("value") == "ref_value" else None):
                if sid is not None and sid not in p.stat_metadata:
                    p.stat_metadata[sid].id = sid
                    p.stat_metadata[sid].name = plane.stat_metadata[sid].name[:TF_OP_CHARS]

        for line in plane.lines:
            if device and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            kept = []
            for ev in line.events:
                name = plane.event_metadata[ev.metadata_id].name
                t = start_ns(line, ev)
                if t_open is not None and not (t_open - 0.05e9 <= t <= t_open + seconds * 1e9):
                    continue
                if host and not (name.startswith(tr.HOST_PREFIXES) or name in (tr.OPEN_MARK, tr.CLOSE_MARK)):
                    continue
                kept.append(ev)
            if line.name == "XLA Ops":
                kept = kept[:: max(1, -(-len(kept) // max_ops))]
            if not kept:
                continue
            ln = p.lines.add()
            ln.id, ln.name, ln.timestamp_ns, ln.display_name = line.id, line.name, line.timestamp_ns, line.display_name
            for ev in kept:
                e = ln.events.add()
                e.metadata_id, e.offset_ps, e.duration_ps = ev.metadata_id, ev.offset_ps, ev.duration_ps
                if host:
                    for st in ev.stats:
                        keep_stat(st, e.stats)
                if ev.metadata_id not in p.event_metadata:
                    src_md = plane.event_metadata[ev.metadata_id]
                    md = p.event_metadata[ev.metadata_id]
                    md.id, md.name = ev.metadata_id, src_md.name[:NAME_CHARS]
                    for st in src_md.stats:
                        if st.metadata_id == stat_ids.get("tf_op"):
                            keep_stat(st, md.stats, TF_OP_CHARS)
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    read = readings(dst, adapter)
    with open(dst_json, "w") as f:
        json.dump(read, f, indent=1)
    print(f"[trim] {os.path.getsize(src)} -> {os.path.getsize(dst)} bytes, {read['grad_steps']} train executions, "
          f"window {read['window_s']:.4f}s, parts {sorted(read['part_seconds'])}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
