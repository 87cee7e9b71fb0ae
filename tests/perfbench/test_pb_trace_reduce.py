"""The reduction from a capture to busy time, per-program time and named idle gaps."""
import json
import os

import numpy as np
import pytest

from perfbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_of_nested_and_overlapping_intervals():
    starts = np.array([0.0, 1.0, 5.0, 5.5, 9.0])
    ends = np.array([4.0, 2.0, 6.0, 7.0, 10.0])
    total, ms, me = tr.union_length(starts, ends)
    assert total == 4.0 + 2.0 + 1.0
    assert ms.tolist() == [0.0, 5.0, 9.0] and me.tolist() == [4.0, 7.0, 10.0]
    assert tr.union_length(np.zeros(0), np.zeros(0))[0] == 0.0


def test_window_marks_clip_and_gaps_take_the_host_spans_name():
    s = 1e9
    planes = {"devices": {"/device:TPU:0": {
        "modules": [("jit_train(1)", 1.0 * s, 2.0 * s), ("jit_train(1)", 4.0 * s, 5.0 * s), ("jit__gather_batch(2)", 5.0 * s, 5.5 * s),
                    ("jit_train(1)", 0.0, 0.5 * s)],
        "ops": [("%while.3 = (f32[]) while(...)", 1.0 * s, 2.0 * s), ("%fusion.7 = f32[8] fusion(...)", 1.0 * s, 1.5 * s),
                ("%fusion.7 = f32[8] fusion(...)", 4.0 * s, 4.25 * s), ("%copy.4 = u8[2] copy(...)", 5.0 * s, 5.5 * s)]}},
        "host": [(tr.OPEN_MARK, "t#0", 1.0 * s, 1.0 * s, {}), (tr.CLOSE_MARK, "t#0", 6.0 * s, 6.0 * s, {}),
                 ("Time/train_time", "t#0", 0.9 * s, 1.1 * s, {}), ("Time/env_interaction_time", "t#1", 2.5 * s, 3.5 * s, {}),
                 # kept for `span_reduce`; the accepted reducer names gaps by `Time/` spans alone
                 ("Wait/learner_queue", "t#0", 2.0 * s, 4.0 * s, {})],
    }
    r = tr.reduce_events(planes)
    assert r["marked"] and r["window_s"] == pytest.approx(5.0)
    assert r["busy_s"] == pytest.approx(1.0 + 1.0 + 0.5)
    assert r["programs"]["jit_train"] == {"seconds": pytest.approx(2.0), "executions": 2}
    assert r["programs"]["jit__gather_batch"]["executions"] == 1
    assert r["top_ops"][0] == ["fusion.7", pytest.approx(0.75)] and all(n != "while.3" for n, _ in r["top_ops"])
    assert r["idle_gaps"][0] == ["Time/env_interaction_time", pytest.approx(2.0)]
    assert r["idle_gaps"][1] == ["unattributed", pytest.approx(0.5)]
    assert r["spans_s"]["Time/train_time"] == pytest.approx(0.1)  # clipped to the window


def test_recorded_chip_trace_reduces_to_the_recorded_numbers():
    """A capture of this PR's own chip run (TPU v5 lite), cut to its first
    events so that it stays small; the numbers were read on the chip's host
    by the same code and are kept beside it."""
    path = os.path.join(HERE, "fixtures", "chip_v5e.xplane.pb")
    with open(os.path.join(HERE, "fixtures", "chip_v5e.json")) as f:
        want = json.load(f)
    got = tr.reduce_file(path)
    assert got["n_device_events"] == want["n_device_events"]
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    # one plane: the mean over planes, the fullest plane's and the only plane's are one number
    assert got["busy_fullest_s"] == got["busy_s"] and list(got["busy_by_plane_s"].values()) == [got["busy_s"]]
    assert {k: v["executions"] for k, v in got["programs"].items()} == {k: v["executions"] for k, v in want["programs"].items()}
    for k, v in want["programs"].items():
        assert got["programs"][k]["seconds"] == pytest.approx(v["seconds"], rel=1e-9)
    assert "jit_train" in got["programs"] and got["top_ops"][0][0] == want["top_ops"][0][0]
    assert got["spans_s"].keys() == want["spans_s"].keys() and "Time/train_time" in got["spans_s"]


def two_planes():
    """The recorded one-chip capture with a second device plane made from it:
    the same programs and ops, but only those of the window's first half, so
    that the second chip is busy for less of it."""
    planes = tr.read_planes(os.path.join(HERE, "fixtures", "chip_v5e.xplane.pb"))
    (name, dev), = planes["devices"].items()
    w0, w1, _ = tr.window_of(planes)
    half = 0.5 * (w0 + w1)
    planes["devices"]["/device:TPU:1"] = {k: [ev for ev in v if ev[2] <= half] for k, v in dev.items()}
    return planes, name


def test_busy_and_idle_are_taken_per_device_plane_and_the_fullest_device_is_the_one_reported():
    planes, first = two_planes()
    one = tr.reduce_file(os.path.join(HERE, "fixtures", "chip_v5e.xplane.pb"))
    two = tr.reduce_events(planes)
    by_plane = two["busy_by_plane_s"]
    assert set(by_plane) == {first, "/device:TPU:1"} and by_plane[first] == one["busy_s"]
    assert 0 < by_plane["/device:TPU:1"] < by_plane[first]
    # pooled into one union (as before this PR) the second plane would add nothing: its events lie under the first's
    assert two["busy_fullest_s"] == one["busy_s"] and two["busy_s"] == pytest.approx(sum(by_plane.values()) / 2)
    # what belongs to one device is the fullest device's, not the sum over planes
    assert two["programs"] == one["programs"] and two["idle_gaps"] == one["idle_gaps"] and two["top_ops"] == one["top_ops"]
    assert two["n_device_events"] > one["n_device_events"] and two["window_s"] == one["window_s"]


def test_the_capture_readers_take_the_fullest_plane_of_the_one_parse():
    from pb_checks import capture_for
    from pb_helpers import XL_CELL
    from perfbench.run import metric_reader

    planes, _ = two_planes()
    cap, reduced = capture_for(XL_CELL, planes), tr.reduce_events(planes)
    one = capture_for(XL_CELL, tr.read_planes(os.path.join(HERE, "fixtures", "chip_v5e.xplane.pb")))
    assert cap.step_executions == one.step_executions > 0 and cap.step_seconds == one.step_seconds
    gs, ge = cap.idle_intervals()
    assert float((ge - gs).sum()) * 1e-9 == pytest.approx(reduced["window_s"] - reduced["busy_fullest_s"], rel=1e-9)
    ctx = {"trace": reduced, "capture": cap, "window": {"grad_steps": 3, "train_calls": 3}}
    assert metric_reader("device.idle_pct")(ctx) == pytest.approx(100 * (1 - reduced["busy_fullest_s"] / reduced["window_s"]))
    assert metric_reader("train_step.device_ms")(ctx) == pytest.approx(1e3 * one.step_seconds / one.step_executions)
