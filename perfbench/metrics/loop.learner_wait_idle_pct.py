"""Share of the traced window in which the learner is inside
`Wait/learner_queue` AND the device is idle: the learner blocked on an empty
packet queue while nothing runs, so how far the host holds the chip back. (The
wait alone says nothing: with the player on the chip the learner also waits
while the device works, and then the device sets the pace.)"""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.span_idle_share_pct(ctx, "Wait/learner_queue")
