"""Device time of the player's decode program (the adapter's `decode_programs`:
one token an env through the latent cache) per call, over the traced window."""


def read(ctx):
    progs = [ctx["trace"]["programs"].get(p) for p in getattr(ctx["adapter"], "decode_programs", ())]
    progs = [p for p in progs if p and p["executions"] > 0]
    if not progs:
        return None
    return 1e3 * sum(p["seconds"] for p in progs) / sum(p["executions"] for p in progs)
