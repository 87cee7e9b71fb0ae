"""Host time of the replay path per gradient step in the traced window:
`Time/learner_apply` (packets into the host buffer), `Time/replay_sync` (new
rows to the device) and `Time/replay_sample` (index draw and gather dispatch)."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.spans_ms_per_grad_step(ctx, ("Time/learner_apply", "Time/replay_sync", "Time/replay_sample"))
