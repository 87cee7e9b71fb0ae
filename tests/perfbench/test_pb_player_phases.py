"""The five readers of the player's act phases on hand-built captures: the device's idle time under each span by interval
intersection, 0 where the span is open only while the device works, nothing where no span of that name occurs."""
import pytest

from pb_checks import capture_for
from pb_helpers import L_CELL, XL_CELL, bench
from perfbench import trace_reduce as tr
from perfbench.run import metric_reader

S = 1e9  # nanoseconds a second
IDLE_READERS = {"player.dispatch_idle_pct": "Player/dispatch", "player.fetch_idle_pct": "Player/fetch",
                "player.env_step_idle_pct": "Player/env_step", "player.record_idle_pct": "Player/record"}
READERS = sorted(IDLE_READERS) + ["player.dispatch_ms"]


def ctx_of(host, busy=((1.0, 2.0), (5.0, 6.0))):
    """What `run.py` hands a reader: a window of 10 s (the marks at 0 and 10 s), the device busy in `busy`, the player's
    spans `host` (name, start s, end s) on one thread."""
    planes = {"devices": {"/device:TPU:0": {"modules": [("jit__step(1)", a * S, b * S) for a, b in busy], "ops": []}},
              "host": [(tr.OPEN_MARK, "t#0", 0.0, 0.0, {}), (tr.CLOSE_MARK, "t#0", 10.0 * S, 10.0 * S, {})]
              + [(n, "t#1", a * S, b * S, {}) for n, a, b in host]}
    return {"window": {"grad_steps": 1, "train_calls": 1}, "capture": capture_for(XL_CELL, planes), "trace": tr.reduce_events(planes)}


# two acts tiled by their children, an env step and a record between them; the device busy 1-2 s and 5-6 s
TWO_ACTS = [("Player/act", 0.5, 3.0), ("Player/prepare", 0.5, 0.7), ("Player/dispatch", 0.7, 1.2), ("Player/fetch", 1.2, 3.0),
            ("Player/env_step", 3.0, 4.0), ("Player/record", 4.0, 5.5),
            ("Player/act", 5.2, 6.5), ("Player/prepare", 5.2, 5.3), ("Player/dispatch", 5.3, 5.9), ("Player/fetch", 5.9, 6.5)]
# idle under each: dispatch 0.7-1.0 (5.3-5.9 is busy); fetch 2.0-3.0 and 6.0-6.5; env step 3.0-4.0; record 4.0-5.0
WANT = {"player.dispatch_idle_pct": 3.0, "player.fetch_idle_pct": 15.0, "player.env_step_idle_pct": 10.0,
        "player.record_idle_pct": 10.0, "player.dispatch_ms": 550.0}


def test_the_readers_are_entries_that_list_the_two_dreamer_cells():
    entries = {m["name"]: m for m in bench()["per_layer"]}
    for name in READERS:
        m = entries[name]
        assert m["workloads"] == [XL_CELL, L_CELL] and m["layer"] == "player" and m["source"] == "program_span"
        assert m["moves"] == "env_steps_per_s" and m["better"] == "lower" and m["unit"] == ("ms" if name.endswith("_ms") else "%")


@pytest.mark.parametrize("metric", READERS)
def test_reader_reads_the_devices_idle_time_under_its_span_by_interval_intersection(metric):
    assert metric_reader(metric)(ctx_of(TWO_ACTS)) == pytest.approx(WANT[metric], rel=1e-9)


@pytest.mark.parametrize("metric", sorted(IDLE_READERS))
def test_reader_reads_zero_where_its_span_is_open_only_while_the_device_is_busy(metric):
    busy_only = [("Player/act", 1.1, 1.6), ("Player/prepare", 1.1, 1.2), ("Player/dispatch", 1.2, 1.3), ("Player/fetch", 1.3, 1.6),
                 ("Player/env_step", 1.6, 1.8), ("Player/record", 1.8, 1.95)]
    assert metric_reader(metric)(ctx_of(busy_only, busy=((1.0, 2.0),))) == 0.0


@pytest.mark.parametrize("metric", READERS)
def test_reader_reads_nothing_where_no_span_of_its_name_occurs(metric):
    """A program older than the span (the act unsplit), a capture that holds none, no capture at all; and a reader hands
    back nothing before it looks past the three keys `trim_scopes.readings` gives it."""
    span = IDLE_READERS.get(metric, "Player/dispatch")
    others = [(n, a, b) for n, a, b in TWO_ACTS if n != span]
    assert metric_reader(metric)(ctx_of(others)) is None
    assert metric_reader(metric)(ctx_of([])) is None
    assert metric_reader(metric)({"window": {"grad_steps": 1, "train_calls": 1}, "capture": None}) is None
    assert metric_reader(metric)({"window": {}, "capture": ctx_of(others)["capture"], "trace": {}}) is None


@pytest.mark.parametrize("metric", READERS)
def test_reader_reads_nothing_on_a_capture_of_the_act_unsplit(metric):
    """The parent of the split has `Player/env_step` and `Player/record` too: the phases are read beside the split alone,
    so that the five come from one program (and a capture of the older program reads as it did)."""
    unsplit = [("Player/act", 0.5, 3.0), ("Player/env_step", 3.0, 4.0), ("Player/record", 4.0, 5.5), ("Player/act", 5.2, 6.5)]
    assert metric_reader(metric)(ctx_of(unsplit)) is None


def test_dispatch_ms_takes_the_median_of_the_spans_wholly_inside_the_window():
    ctx = ctx_of([("Player/dispatch", -0.5, 0.5), ("Player/dispatch", 2.0, 2.001), ("Player/dispatch", 3.0, 3.003),
                  ("Player/dispatch", 4.0, 4.002), ("Player/dispatch", 9.9, 10.9)])
    assert metric_reader("player.dispatch_ms")(ctx) == pytest.approx(2.0)
