"""Share of the traced window inside `Wait/learner_queue`: the learner blocked
on an empty packet queue, so how host-bound the cell is."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.span_share_pct("Wait/learner_queue")
