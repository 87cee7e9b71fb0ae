"""Share of the rows the grouped expert products multiplied that held a routed
(token, expert) pair, over the run's `moe_load` events (the program's own
counter, from numbers its update returns): the rest is the padding a dropless
layer with static shapes carries."""
from perfbench import program_events


def read(ctx):
    loads = program_events.events(ctx, "moe_load")
    rows = sum(e["rows"] for e in loads)
    return 100.0 * sum(e["routed_here"] for e in loads) / rows if rows else None
