"""Share of the traced window inside `Wait/player_queue`: the player blocked on
a full packet queue or the staleness gate, so how learner-bound the cell is."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.span_share_pct(ctx, "Wait/player_queue")
