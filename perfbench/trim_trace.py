"""Cut a capture down to a fixture a repository can carry:

    python3 perfbench/trim_trace.py <in.xplane.pb> <out.xplane.pb> <out.json> [seconds] [max_ops]

Keeps, from the first window mark on and for `seconds`: the device planes'
`XLA Modules` events, the first `max_ops` events of their `XLA Ops` line, and
the host plane's spans and window marks; drops every stat and every unused
name. Then reduces the cut file with `trace_reduce` and writes the numbers
beside it, for the test that reduces it again. Needs TensorFlow's xplane
protobuf bindings (they are on the chip's machine as they are here).
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from perfbench import trace_reduce as tr

    src, dst, dst_json = argv[:3]
    seconds = float(argv[3]) if len(argv) > 3 else 1.5
    max_ops = int(argv[4]) if len(argv) > 4 else 2500
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
        for line in plane.lines:
            if device and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            kept = []
            for ev in line.events:
                name = plane.event_metadata[ev.metadata_id].name
                t = start_ns(line, ev)
                if t_open is not None and not (t_open - 0.05e9 <= t <= t_open + seconds * 1e9):
                    continue
                if host and not (name.startswith(tr.SPAN_PREFIX) or name in (tr.OPEN_MARK, tr.CLOSE_MARK)):
                    continue
                kept.append(ev)
            if line.name == "XLA Ops":
                kept = kept[:max_ops]
            if not kept:
                continue
            ln = p.lines.add()
            ln.id, ln.name, ln.timestamp_ns = line.id, line.name, line.timestamp_ns
            ln.display_name = line.display_name
            for ev in kept:
                e = ln.events.add()
                e.metadata_id, e.offset_ps, e.duration_ps = ev.metadata_id, ev.offset_ps, ev.duration_ps
                if ev.metadata_id not in p.event_metadata:
                    md = p.event_metadata[ev.metadata_id]
                    md.id = ev.metadata_id
                    md.name = plane.event_metadata[ev.metadata_id].name[:160]
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    reduced = tr.reduce_file(dst)
    with open(dst_json, "w") as f:
        json.dump(reduced, f, indent=1)
    print(f"[trim] {os.path.getsize(src)} -> {os.path.getsize(dst)} bytes, {reduced['n_device_events']} device events, "
          f"window {reduced['window_s']:.4f}s busy {reduced['busy_s']:.4f}s programs {sorted(reduced['programs'])}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
