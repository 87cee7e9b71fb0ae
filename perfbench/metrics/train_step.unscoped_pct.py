"""Share of jit(train)'s summed op time in the traced window under none of
the eight scope names of `make_train_fn` (span_reduce.PARTS)."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.unscoped_pct(ctx)
