"""Spans with counts: summed in the tracker beside seconds and calls, stats of
the annotation in a profiler capture under the span's bare name, and the
layer-boundary spans of the shared code (parameter mirror, replay ring,
overlap engine) fed from that one stopwatch."""
import glob
import os
import textwrap
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.telemetry.schema import SPAN_PREFIXES, SPAN_SCHEMAS
from sheeprl_tpu.telemetry.spans import GLOBAL_TRACKER, Span, SpanTracker


def test_counts_are_summed_per_name_and_drained_with_the_seconds():
    tr = SpanTracker()
    for g in (1, 2, 4):
        with tr.span("Time/train_time", grad_steps=g, burst=7):
            pass
    with tr.span("Time/replay_stage"):
        pass
    assert tr.sums() == {"Time/train_time": {"grad_steps": 7, "burst": 21}}
    assert tr.counts() == {"Time/train_time": 3, "Time/replay_stage": 1}
    assert set(tr.compute(reset=True)) == {"Time/train_time", "Time/replay_stage"}
    assert tr.sums() == {} and tr.counts() == {} and tr.compute() == {}


def test_a_count_known_only_inside_the_span_and_the_elapsed_time_after_it():
    tr = SpanTracker()
    with tr.span("Wait/learner_queue") as wait:
        time.sleep(0.01)
        wait.count(packets=3)
    assert tr.sums()["Wait/learner_queue"] == {"packets": 3}
    assert wait.elapsed >= 0.01 and wait.elapsed == pytest.approx(tr.compute()["Wait/learner_queue"])


def test_a_disabled_span_keeps_its_stopwatch_and_stays_out_of_the_tracker():
    tr = SpanTracker()
    with tr.span("Wait/player_queue", enabled=False) as gate:
        time.sleep(0.005)
        gate.count(packets=1)
    assert gate.elapsed >= 0.005 and tr.compute() == {} and tr.sums() == {}


def test_the_facade_passes_counts_through(tmp_path):
    from sheeprl_tpu.telemetry import Telemetry

    telem = Telemetry(None, str(tmp_path), tracker=SpanTracker())
    with telem.span("Time/learner_apply", env_steps=4, packets=2):
        pass
    assert telem.tracker.sums() == {"Time/learner_apply": {"env_steps": 4, "packets": 2}}
    telem.close()


def test_counts_are_stats_of_the_annotation_and_the_name_stays_bare(tmp_path):
    """The profiler carries an annotation's counts inside its name
    (`name#k=v#`) and strips them into stats when it writes the plane: the
    accepted reducer, which looks names up as they are, must still find them."""
    from jax.profiler import ProfileData

    from perfbench import trace_reduce

    jax.profiler.start_trace(str(tmp_path))
    try:
        with Span("Time/train_time", tracker=SpanTracker(), grad_steps=3, burst=5):
            jnp.ones((8, 8)).sum().block_until_ready()
        with Span("Wait/learner_queue", tracker=SpanTracker()) as wait:
            wait.count(packets=2)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)[-1]
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIXES) or "#" in ev.name:
                    found[ev.name] = dict(ev.stats)
    assert found["Time/train_time"] == {"grad_steps": 3, "burst": 5}
    assert found["Wait/learner_queue"] == {"packets": 2}
    assert "Time/train_time" in trace_reduce.reduce_file(path)["spans_s"]


def test_every_registered_span_keeps_to_the_prefix_rule():
    assert all(name.startswith(SPAN_PREFIXES) for name in SPAN_SCHEMAS)
    assert {"Time/train_time", "Time/env_interaction_time", "Time/param_refresh", "Wait/learner_queue",
            "Wait/player_queue", "Player/act", "Player/env_step", "Player/record"} <= set(SPAN_SCHEMAS)


def _lint(tmp_path, source):
    from sheeprl_tpu.analysis.engine import run_paths
    from sheeprl_tpu.analysis.rules.telemetry_schema import TelemetrySchemaRule

    f = tmp_path / "mod.py"
    f.write_text(textwrap.dedent(source))
    return run_paths([f], [TelemetrySchemaRule(schema={})])


def test_lint_wants_a_span_and_its_counts_in_the_registry(tmp_path):
    findings = _lint(tmp_path, """
        def loop(telem, g):
            with telem.span("Time/train_time", grad_steps=g, burst=1):
                pass
            with telem.span("Time/made_up"):
                pass
            with telem.span("Time/train_time", frames=g):
                pass
            with Span("Wait/nobody"):
                pass
            with telem.span("other/prefix"):
                pass
        """)
    assert [x.line for x in findings] == [5, 7, 9]
    assert "'Time/made_up' is not declared" in findings[0].message
    assert "count 'frames' is not declared" in findings[1].message  # (`tokens` is declared since PR 38)


@pytest.mark.parametrize("same_device", [1, 0], ids=["learners_device", "another_device"])
def test_param_mirror_refresh_is_a_span_with_the_bytes_it_copied(same_device):
    """`same_device` says whether the copy stayed on the device the parameters
    live on (the span then times one dispatch) or crossed to another."""
    from sheeprl_tpu.parallel.placement import ParamMirror

    params = {"w": jnp.ones((4, 8)), "b": jnp.zeros((8,))}
    mirror = ParamMirror(params, jax.devices()[0 if same_device else 1])
    GLOBAL_TRACKER.compute(reset=True)
    mirror.refresh(params)
    mirror.refresh(params)
    assert GLOBAL_TRACKER.counts()["Time/param_refresh"] == 2
    assert GLOBAL_TRACKER.sums()["Time/param_refresh"] == {
        "bytes": 2 * (32 + 8) * 4, "leaves": 4, "same_device": 2 * same_device,
    }
    assert set(SPAN_SCHEMAS["Time/param_refresh"]) == {"bytes", "leaves", "same_device"}
    GLOBAL_TRACKER.compute(reset=True)


def test_make_param_mirror_leaves_a_placement_event_the_schema_accepts():
    from sheeprl_tpu.config import Config
    from sheeprl_tpu.parallel import placement
    from sheeprl_tpu.telemetry.schema import validate_event

    params = {"w": jnp.ones((4, 8)), "b": jnp.zeros((8,))}
    mirror, pdev, _, _ = placement.make_param_mirror(Config({"algo": {}}), jax.devices()[0], params, jax.random.key(0))
    rec = mirror.placement
    assert validate_event(rec) == []
    assert rec["tree_bytes"] == (32 + 8) * 4 and rec["threshold_bytes"] == placement.AUTO_ACCELERATOR_MIN_BYTES
    assert rec["player_device"] == rec["learner_device"] == f"cpu:{pdev.id}" and rec["mode"] == "auto"
    assert rec["same_device"] == 1


def test_ring_sync_counts_the_rows_it_ships_and_sampling_its_gradient_steps():
    from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer
    from sheeprl_tpu.data.device_ring import DeviceRingPrefetcher

    rb = EnvIndependentReplayBuffer(32, n_envs=2, obs_keys=("obs",), buffer_cls=SequentialReplayBuffer, seed=0)
    for t in range(12):
        rb.add({"obs": np.full((1, 2, 3), t, np.float32), "rewards": np.zeros((1, 2, 1), np.float32)})
    ring = DeviceRingPrefetcher(rb, batch_size=2, sequence_length=4)
    GLOBAL_TRACKER.compute(reset=True)
    ring.take(3)  # first sync ships the 24 stored rows, then one [3, 4, 2] gather
    ring.take(1)  # ships each env's newest row again (restart surgery may have edited it)
    sums, calls = GLOBAL_TRACKER.sums(), GLOBAL_TRACKER.counts()
    assert calls["Time/replay_sync"] == 2 and sums["Time/replay_sync"]["rows"] == 24 + 2
    assert sums["Time/replay_sync"]["bytes"] >= 26 * (3 + 1) * 4
    ring.resync()
    rb2 = EnvIndependentReplayBuffer(8, n_envs=1, obs_keys=("obs",), buffer_cls=SequentialReplayBuffer, seed=0)
    DeviceRingPrefetcher(rb2, batch_size=1, sequence_length=2).sync()  # an empty buffer has nothing pending: no span
    assert GLOBAL_TRACKER.counts()["Time/replay_sync"] == 2
    assert calls["Time/replay_sample"] == 2 and sums["Time/replay_sample"] == {"grad_steps": 4}
    GLOBAL_TRACKER.compute(reset=True)


def test_overlap_engine_books_the_elapsed_time_of_its_spans_and_nothing_else():
    """One stopwatch: the `overlap` event's busy and stall seconds are the
    tracker's totals of the spans the engine opens."""
    from sheeprl_tpu.engine import OverlapEngine, Packet

    events = []

    class Telem:
        tracker = SpanTracker()

        def span(self, name, **counts):
            return Span(name, tracker=self.tracker, **counts)

        def emit(self, rec):
            events.append(rec)

    telem = Telem()
    eng = OverlapEngine(enabled=True, queue_depth=2, total_steps=24, telem=telem, trace_spans=False, stats_every_s=1e9)
    release = threading.Event()

    def play():
        time.sleep(0.01)
        return Packet(None, 2)

    eng.start(play)
    taken = 0
    while True:
        if taken == 6:  # let the queue fill once, so the player waits too
            release.wait(0.15)
        pkts = eng.take()
        if not pkts:
            break
        taken += sum(p.env_steps for p in pkts)
        eng.published()
    eng.shutdown()
    rec = [e for e in events if e["event"] == "overlap"][-1]
    totals, sums = telem.tracker.compute(), telem.tracker.sums()
    assert taken == 24 and sums["Time/env_interaction_time"]["env_steps"] == 24
    assert "version" in sums["Time/env_interaction_time"]
    assert rec["learner_stall_s"] == pytest.approx(totals["Wait/learner_queue"], abs=2e-6) and rec["learner_stall_s"] > 0
    assert rec["player_stall_s"] == pytest.approx(totals["Wait/player_queue"], abs=2e-6) and rec["player_stall_s"] > 0
    assert rec["player_busy_s"] == pytest.approx(totals["Time/env_interaction_time"], abs=2e-6)
    assert sums["Wait/learner_queue"]["packets"] >= 1
