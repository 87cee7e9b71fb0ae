"""The decode step's share of its roofline: the bytes one step for all envs
must read (every parameter it touches once and the cache rows in use: the
adapter's `decode_bytes`, from shapes) over the chip's HBM bandwidth, over the
decode program's device time per call. Bound by bytes: 32 tokens a step reuse
each weight 32 times."""


def read(ctx):
    adapter = ctx["adapter"]
    progs = [ctx["trace"]["programs"].get(p) for p in getattr(adapter, "decode_programs", ())]
    progs = [p for p in progs if p and p["executions"] > 0 and p["seconds"] > 0]
    if not progs or ctx["rehearse"]:
        return None
    per_call = sum(p["seconds"] for p in progs) / sum(p["executions"] for p in progs)
    peak = ctx["peaks"].lookup(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (adapter.decode_bytes(ctx["shapes"], ctx["spec"]) / peak) / per_call
