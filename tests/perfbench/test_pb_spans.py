"""The program's spans in a capture, end to end on the CPU: one rehearsal of
the harness whose learner thread is tiled by `Time/` and `Wait/` spans and
whose counts add up, and one short run of the program itself in which every
span the DreamerV3 loop owes occurs (and none that the table lacks) and the overlap engine's stall seconds are the
tracker's."""
import glob
import json
import os

import pytest

from pb_checks import capture_for, spans_are_registered_and_the_owed_occur
from pb_helpers import XL_CELL
from perfbench import span_reduce, trace_reduce
from sheeprl_tpu.telemetry.schema import SPAN_SCHEMAS

# the spans the DreamerV3 loop owes, written out as that loop's own list: the fourteen the table had while it was the
# only loop instrumented (PR 28). A name registered since for another loop is in the table and in no run of this one
DREAMER_V3_OWES = ["Time/env_interaction_time", "Time/train_time", "Time/learner_apply", "Time/replay_sync", "Time/replay_sample",
                   "Time/replay_stage", "Time/param_refresh", "Time/log_flush", "Time/checkpoint", "Wait/learner_queue",
                   "Wait/player_queue", "Player/act", "Player/env_step", "Player/record"]
STEADY = ["Time/train_time", "Time/env_interaction_time", "Time/learner_apply", "Time/replay_sync", "Time/replay_sample",
          "Time/replay_stage", "Time/param_refresh", "Player/act", "Player/env_step", "Player/record"]


@pytest.fixture(scope="module")
def rehearsal(xl_rehearsal):
    rc, _, err, keep = xl_rehearsal
    assert rc == 0, err[-3000:]
    path = glob.glob(os.path.join(keep, "*.xplane.pb"))[0]
    with open(glob.glob(os.path.join(keep, "*_t1.json"))[0]) as f:
        window = json.load(f)["window"]
    planes = trace_reduce.read_planes(path)
    return capture_for(XL_CELL, planes), trace_reduce.reduce_events(planes), window


def test_the_steady_loops_spans_are_all_in_the_window_under_their_bare_names(rehearsal):
    cap, reduced, _ = rehearsal
    assert all(cap.spans(name) for name in STEADY), [n for n in STEADY if not cap.spans(n)]
    assert cap.spans("Wait/player_queue") or cap.spans("Wait/learner_queue")  # one side always waits for the other
    # the accepted reducer looks names up as they are: the counts must not have changed them
    assert reduced["marked"] and {"Time/train_time", "Time/env_interaction_time"} <= set(reduced["spans_s"])
    assert set(n for n, *_ in cap.host) <= set(SPAN_SCHEMAS)


def test_learner_thread_is_tiled_by_time_and_wait_spans(rehearsal):
    cap, _, _ = rehearsal
    learner = cap.learner_thread()
    mine = sorted((s, e, n) for n, th, s, e, _ in cap.host if th == learner and e > cap.w0 and s < cap.w1)
    assert mine and all(n.startswith(("Time/", "Wait/")) for _, _, n in mine)  # no Player/ step on the learner's thread
    top, open_until = [], -1.0
    for s, e, n in mine:
        if s >= open_until:  # a span at depth 0: it starts after the last one ended
            top.append((s, e, n))
            open_until = e
        else:  # a child: it must lie inside its parent, or two spans overlap at one depth
            assert e <= open_until, (n, "overlaps the span before it")
    covered = sum(min(e, cap.w1) - max(s, cap.w0) for s, e, _ in top) * 1e-9
    assert covered / cap.window_s >= 0.95, (covered, cap.window_s)


def test_counts_on_the_spans_add_up_to_the_windows_work(rehearsal):
    cap, _, window = rehearsal
    inside = [st for _, s, _, st in cap.spans("Time/train_time") if s >= cap.w0]
    # every call of this cell takes one gradient step, and the call that closes the window also stops
    # the capture from inside its own span, so that one span is never written
    assert window["grad_steps"] == window["train_calls"]
    assert sum(st["grad_steps"] for st in inside) == window["grad_steps"] - 1
    bursts = [st["burst"] for st in inside]
    assert bursts == sorted(bursts) and bursts[-1] > bursts[0]
    acted = [st for _, s, e, st in cap.spans("Time/env_interaction_time") if s >= cap.w0 and e <= cap.w1]
    assert abs(sum(st["env_steps"] for st in acted) - window["env_steps"]) <= 2
    assert all(st["version"] <= bursts[-1] + 1 for st in acted)
    assert all(st["bytes"] > 0 and st["leaves"] > 0 for *_, st in cap.spans("Time/param_refresh"))
    assert all(st["rows"] >= 1 and st["bytes"] > 0 for *_, st in cap.spans("Time/replay_sync"))


def test_new_readers_read_the_rehearsals_capture(rehearsal):
    cap, _, window = rehearsal
    ctx = {"capture": cap, "window": window}
    assert 0 < span_reduce.span_share_pct(ctx, "Time/param_refresh") <= 100
    assert span_reduce.span_median_ms(ctx, "Player/act") > 0
    assert span_reduce.spans_ms_per_grad_step(ctx, ("Time/learner_apply", "Time/replay_sync", "Time/replay_sample")) > 0
    assert 0 <= span_reduce.span_share_pct(ctx, "Wait/learner_queue") <= 100  # 0.0 where the learner never waited
    # no device plane in a CPU capture: the whole window is idle, so the wait while idle is the wait
    assert span_reduce.span_idle_share_pct(ctx, "Wait/learner_queue") == pytest.approx(span_reduce.span_share_pct(ctx, "Wait/learner_queue"))
    assert span_reduce.part_ms(ctx, "wm_encoder") is None and span_reduce.step_ms(ctx) is None  # the CPU's plane has no `XLA Ops` line


def test_every_span_the_loop_owes_occurs_and_the_engines_stalls_are_the_trackers():
    import jax

    from sheeprl_tpu.cli import run

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace("trace", profiler_options=opts)
    try:
        run([
            "exp=dreamer_v3", "env=dummy", "env.id=discrete_dummy", "env.num_envs=2", "env.sync_env=True",
            "env.capture_video=False", "algo=dreamer_v3_XS", "algo.total_steps=384", "algo.learning_starts=64",
            "algo.replay_ratio=0.25", "algo.per_rank_batch_size=2", "algo.per_rank_sequence_length=2", "algo.horizon=4",
            "algo.dense_units=16", "algo.world_model.encoder.cnn_channels_multiplier=2",
            "algo.world_model.recurrent_model.recurrent_state_size=16", "algo.world_model.transition_model.hidden_size=16",
            "algo.world_model.representation_model.hidden_size=16", "algo.world_model.discrete_size=4",
            "algo.world_model.stochastic_size=4", "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[]",
            "algo.run_test=False", "algo.overlap.enabled=True", "algo.overlap.stats_every_s=0.5", "buffer.size=512",
            "buffer.memmap=False", "buffer.device_cache=true", "metric.log_level=1", "metric.log_every=64",
            "checkpoint.every=128", "checkpoint.save_last=False", "model_manager.disabled=True", "run_name=spans_dv3",
        ])
    finally:
        jax.profiler.stop_trace()
    cap = capture_for(XL_CELL, trace_reduce.read_dir("trace"))
    spans_are_registered_and_the_owed_occur({n for n, *_ in cap.host}, DREAMER_V3_OWES, SPAN_SCHEMAS)
    learner = cap.learner_thread()
    player = {th for n, th, *_ in cap.host if n.startswith("Player/")}
    assert len(player) == 1 and learner not in player
    assert {th for n, th, *_ in cap.host if n == "Wait/player_queue"} == player
    assert {th for n, th, *_ in cap.host if n == "Wait/learner_queue"} == {learner}

    # one stopwatch: over the run the `overlap` events' seconds are the spans' (the `log` events drain the tracker)
    stream = "logs/runs/dreamer_v3/discrete_dummy/spans_dv3/version_0/telemetry.jsonl"
    events = [json.loads(ln) for ln in open(stream)]
    spans = [e["spans"] for e in events if e["event"] in ("log", "shutdown")]
    overlap = [e for e in events if e["event"] == "overlap"]
    for field, name in (("learner_stall_s", "Wait/learner_queue"), ("player_stall_s", "Wait/player_queue"),
                        ("player_busy_s", "Time/env_interaction_time")):
        booked, timed = sum(e[field] for e in overlap), sum(s.get(name, 0.0) for s in spans)
        assert booked > 0 and booked == pytest.approx(timed, abs=1e-5 * (len(overlap) + len(spans))), (field, booked, timed)


def test_the_loops_own_list_is_the_tables_fourteen_and_every_name_is_still_registered():
    assert len(set(DREAMER_V3_OWES)) == 14 and set(DREAMER_V3_OWES) <= set(SPAN_SCHEMAS)


def test_a_span_registered_for_another_loop_fails_neither_half_and_a_missing_or_unregistered_one_fails():
    """Obstacle 2 of ISSUE 37, as cases: until then the run's names had to EQUAL the table, so a fifteenth name
    registered for another loop (PR 36: `Player/prefill`, `Time/cache_stage`) failed every DreamerV3 run."""
    seen = set(DREAMER_V3_OWES)
    longer = dict(SPAN_SCHEMAS, **{"Player/prefill": ("tokens",), "Time/cache_stage": ()})
    assert sorted(seen) != sorted(longer)  # what the parent's line compared
    spans_are_registered_and_the_owed_occur(seen, DREAMER_V3_OWES, longer)
    for missing in ("Time/checkpoint", "Wait/player_queue", "Player/record"):
        with pytest.raises(AssertionError, match=missing):
            spans_are_registered_and_the_owed_occur(seen - {missing}, DREAMER_V3_OWES, longer)
    with pytest.raises(AssertionError, match="Player/unheard_of"):
        spans_are_registered_and_the_owed_occur(seen | {"Player/unheard_of"}, DREAMER_V3_OWES, longer)
