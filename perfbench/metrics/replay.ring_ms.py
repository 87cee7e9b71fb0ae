"""Device time of jit(_gather_batch) + jit(_scatter_rows) in the traced window,
per gradient step."""


def read(ctx):
    progs = ctx["trace"]["programs"]
    g = ctx["window"]["grad_steps"]
    found = [progs[p]["seconds"] for p in ("jit__gather_batch", "jit__scatter_rows") if p in progs]
    if not found or g <= 0:
        return None
    return 1e3 * sum(found) / g
