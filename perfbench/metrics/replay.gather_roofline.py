"""Bytes a [G, T, B] gather must read and write (from shapes, work.py) over
the chip's HBM bandwidth, over the gather's device time in the trace.
Bound by bytes: a gather does no arithmetic."""


def read(ctx):
    prog = ctx["trace"]["programs"].get("jit__gather_batch")
    if not prog or prog["seconds"] <= 0 or ctx["rehearse"]:
        return None
    work, spec = ctx["work"], ctx["spec"]
    w = spec["config"]["widths"]
    items = work.ring_items(spec["mix"], int(spec["mix"]["action"]["n"]))
    nbytes = work.gather_bytes(items, 1, int(w["per_rank_sequence_length"]), int(w["per_rank_batch_size"]))
    peak = ctx["peaks"].lookup(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (nbytes * prog["executions"] / peak) / prog["seconds"]
