"""`span_reduce` on recorded v5e captures: a cut of this PR's own chip run
(`chip_v5e_scopes.xplane.pb`, cut by `perfbench/trim_scopes.py`, the numbers
read on it kept beside it) and the accepted fixture of the parent's run, which
has neither scopes nor the layer-boundary spans."""
import json
import os
import shutil

import pytest

from pb_helpers import bench
from perfbench import span_reduce, trace_reduce
from perfbench.run import metric_reader

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPES = os.path.join(HERE, "fixtures", "chip_v5e_scopes.xplane.pb")
PARENT = os.path.join(HERE, "fixtures", "chip_v5e.xplane.pb")
with open(os.path.join(HERE, "fixtures", "chip_v5e_scopes.json")) as _f:
    WANT = json.load(_f)
NEW = sorted(WANT["metrics"])


def as_run_py_leaves_it(capture, tmp_path):
    """The capture under ./trace of the working directory, which is all a reader is told."""
    os.makedirs(tmp_path / "trace" / "plugins" / "profile" / "run")
    shutil.copy(capture, tmp_path / "trace" / "plugins" / "profile" / "run" / "host.xplane.pb")


def test_the_fixture_names_every_metric_this_pr_added():
    entries = {m["name"]: m for m in bench()["per_layer"]}
    assert len(NEW) == 14 and set(NEW) <= set(entries)
    assert all(entries[n]["workloads"] == ["dv3_xl.crafter", "dv3_l.navigate4"] for n in NEW)
    assert os.path.getsize(SCOPES) < 400_000


@pytest.mark.parametrize("metric", NEW)
def test_reader_reads_the_recorded_number_on_the_cut_of_this_prs_chip_run(metric, tmp_path):
    as_run_py_leaves_it(SCOPES, tmp_path)
    got = metric_reader(metric)({"window": {"grad_steps": WANT["grad_steps"]}})
    assert got == pytest.approx(WANT["metrics"][metric], rel=1e-9, abs=1e-12)


@pytest.mark.parametrize("metric", NEW)
def test_reader_returns_none_on_the_parents_capture_and_where_there_is_no_capture(metric, tmp_path):
    assert metric_reader(metric)({"window": {"grad_steps": 3}}) is None  # no ./trace at all
    other = tmp_path / "parent"
    other.mkdir()
    os.chdir(other)  # another working directory: the capture is looked up and memoised by it
    as_run_py_leaves_it(PARENT, other)
    assert metric_reader(metric)({"window": {"grad_steps": 3}}) is None


def test_the_capture_is_parsed_once_per_process_and_working_directory(tmp_path):
    as_run_py_leaves_it(SCOPES, tmp_path)
    assert span_reduce.load() is span_reduce.load()


def test_ops_are_booked_to_parts_by_the_tf_op_of_their_metadata():
    tf_ops = span_reduce.read_tf_ops(SCOPES)
    assert sum(v.startswith("jit(train)/") for v in tf_ops.values()) > 400  # the gather's and the scatter's few beside them
    assert any("transpose(jvp(wm_rssm))" in v for v in tf_ops.values())  # the backward of a part keeps its name
    cap = span_reduce.Capture(SCOPES)
    by_part = cap.part_seconds()
    assert cap.scoped and set(by_part) == set(span_reduce.PARTS) | {None}
    assert by_part == pytest.approx({(None if k == "None" else k): v for k, v in WANT["part_seconds"].items()}, rel=1e-9)
    # wrappers hold the ops of a body, so none is booked; every booked op ran inside an execution of jit_train
    assert all(n.split(".", 1)[0] not in trace_reduce.WRAPPERS for _, n, _ in cap.train_ops)
    runs = trace_reduce.reduce_file(SCOPES)["programs"]["jit_train"]
    assert sum(by_part.values()) <= runs["seconds"] and runs["executions"] == WANT["grad_steps"]
    assert max(by_part, key=by_part.get) == "wm_rssm"


def test_host_spans_keep_their_thread_and_their_counts():
    cap = span_reduce.Capture(SCOPES)
    learner = cap.learner_thread()
    threads = {th for _, th, *_ in cap.host}
    assert len(threads) == 2 and learner in threads  # two lines, both named python3
    assert {n for n, th, *_ in cap.host if th != learner} == {
        "Time/env_interaction_time", "Wait/player_queue", "Player/act", "Player/env_step", "Player/record"}
    refresh = cap.spans("Time/param_refresh")
    assert refresh and all(st["bytes"] == 822949460 and st["leaves"] == 125 for *_, st in refresh)
    assert all("grad_steps" in st and "burst" in st for *_, st in cap.spans("Time/train_time"))
    assert cap.instrumented and not span_reduce.Capture(PARENT).instrumented
    # the accepted reducer still finds its two spans under their bare names, counts and all
    assert {"Time/train_time", "Time/env_interaction_time"} <= set(trace_reduce.reduce_file(SCOPES)["spans_s"])


def test_trim_scopes_reads_back_what_it_wrote():
    from perfbench import trim_scopes

    assert trim_scopes.readings(SCOPES)["metrics"] == pytest.approx(WANT["metrics"], rel=1e-9, abs=1e-12)
