"""Share of the traced window's idle time (not of the window) that no
`Time/`, `Wait/` or `Player/` span on any thread covers."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.idle_unattributed_pct(ctx)
