"""memory_stats()["peak_bytes_in_use"] after the window, before the reference runs."""


def read(ctx):
    return ctx["peak_bytes"] / 2**30 if ctx["peak_bytes"] else None
