"""`span_reduce` on recorded v5e captures: a cut of this PR's own chip run
(`chip_v5e_scopes.xplane.pb`, cut by `perfbench/trim_scopes.py`, the numbers
read on it kept beside it) and the accepted fixture of the parent's run, which
has neither scopes nor the layer-boundary spans."""
import json
import os

import pytest

from pb_checks import capture_for, spec_and_adapter
from pb_helpers import CELLS, L_CELL, XL_CELL, bench
from perfbench import span_reduce, trace_reduce
from perfbench.run import metric_reader

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPES = os.path.join(HERE, "fixtures", "chip_v5e_scopes.xplane.pb")
PARENT = os.path.join(HERE, "fixtures", "chip_v5e.xplane.pb")
with open(os.path.join(HERE, "fixtures", "chip_v5e_scopes.json")) as _f:
    WANT = json.load(_f)
NEW = sorted(WANT["metrics"])


def ctx_of(path, grad_steps):
    """What `run.py` hands a reader of a capture: the one parse, reduced both ways."""
    planes = trace_reduce.read_planes(path)  # (both captures are runs of the XL cell: its adapter's programs and parts)
    return {"window": {"grad_steps": grad_steps, "train_calls": grad_steps}, "trace": trace_reduce.reduce_events(planes),
            "capture": capture_for(XL_CELL, planes), "trace_dir": os.path.dirname(path)}


@pytest.fixture(scope="module")
def scopes_ctx():
    return ctx_of(SCOPES, WANT["grad_steps"])


@pytest.fixture(scope="module")
def parent_ctx():
    return ctx_of(PARENT, 3)


def test_the_fixture_names_every_metric_that_reads_through_span_reduce():
    entries = {m["name"]: m for m in bench()["per_layer"]}
    # PR 28's fourteen, `loop.learner_wait_pct` replaced by `loop.learner_wait_idle_pct`, and `train_step.device_ms`
    assert len(NEW) == 15 and set(NEW) <= set(entries) and "loop.learner_wait_pct" not in entries
    # both accepted cells report each of them; a later cell adds its name to the lists of those it reports
    assert all({XL_CELL, L_CELL} <= set(entries[n]["workloads"]) <= set(CELLS) for n in NEW)
    assert os.path.getsize(SCOPES) < 400_000


@pytest.mark.parametrize("metric", NEW)
def test_reader_reads_the_recorded_number_on_the_cut_of_this_prs_chip_run(metric, scopes_ctx):
    got = metric_reader(metric)(scopes_ctx)
    assert got == pytest.approx(WANT["metrics"][metric], rel=1e-9, abs=1e-12)


@pytest.mark.parametrize("metric", NEW)
def test_reader_returns_none_on_the_parents_capture_and_where_there_is_no_capture(metric, parent_ctx):
    assert metric_reader(metric)({"window": {"grad_steps": 3, "train_calls": 3}, "capture": None}) is None
    if metric == "train_step.device_ms":  # the step's program is in any capture of a run; PR 27's reader read it there too
        assert metric_reader(metric)(parent_ctx) == pytest.approx(175.97, abs=0.05)
    else:
        assert metric_reader(metric)(parent_ctx) is None


def test_the_capture_is_parsed_once_and_both_reducers_read_that(monkeypatch):
    from jax.profiler import ProfileData

    parses = []
    real = ProfileData.from_file
    monkeypatch.setattr(ProfileData, "from_file", staticmethod(lambda path: parses.append(path) or real(path)))
    ctx = ctx_of(SCOPES, WANT["grad_steps"])
    for name in NEW + ["device.idle_pct", "replay.ring_ms", "loop.train_span_pct"]:  # every reader of the capture
        metric_reader(name)(ctx)
    assert parses == [SCOPES]


def test_learner_wait_counts_only_while_the_device_is_idle(scopes_ctx):
    cap = scopes_ctx["capture"]
    idle_pct = metric_reader("device.idle_pct")(scopes_ctx)
    for name in ("Wait/learner_queue", "Wait/player_queue", "Time/param_refresh"):
        both, alone = span_reduce.span_idle_share_pct(scopes_ctx, name), span_reduce.span_share_pct(scopes_ctx, name)
        assert 0.0 <= both <= min(alone, idle_pct) + 1e-9, name
    # the refresh of that run held the device idle (PERF.md, PR 28): nearly all of its idle time lies under it
    assert span_reduce.span_idle_share_pct(scopes_ctx, "Time/param_refresh") > 0.9 * idle_pct
    assert cap.window_s == pytest.approx(WANT["window_s"])


def test_times_per_step_divide_by_the_whole_executions_in_the_window(scopes_ctx):
    cap = scopes_ctx["capture"]
    assert cap.step_executions == WANT["whole_executions"] == 3
    assert metric_reader("train_step.device_ms")(scopes_ctx) == pytest.approx(1e3 * cap.step_seconds / 3)
    # a window that cuts an execution: neither its time nor its ops count, and the reading stays the step's
    planes = trace_reduce.read_planes(SCOPES)
    runs = sorted((s, e) for n, s, e in next(iter(planes["devices"].values()))["modules"] if n.startswith("jit_train"))
    cut = 0.5 * (runs[-1][0] + runs[-1][1])
    planes["host"] = [ev for ev in planes["host"] if ev[0] != trace_reduce.CLOSE_MARK] + [(trace_reduce.CLOSE_MARK, "t#0", cut, cut, {})]
    short = capture_for(XL_CELL, planes)
    assert short.step_executions == 2
    ctx = {**scopes_ctx, "capture": short, "window": {"grad_steps": 2, "train_calls": 2}}
    assert metric_reader("train_step.device_ms")(ctx) == pytest.approx(WANT["metrics"]["train_step.device_ms"], rel=0.01)
    # (the fixture keeps every k-th op, so an execution's share of the kept ops varies; half an execution more would read 1.25x)
    assert metric_reader("train_step.wm_rssm_ms")(ctx) == pytest.approx(WANT["metrics"]["train_step.wm_rssm_ms"], rel=0.1)


def test_ops_are_booked_to_parts_by_the_tf_op_of_their_metadata():
    tf_ops = span_reduce.read_tf_ops(SCOPES)
    assert sum(v.startswith("jit(train)/") for v in tf_ops.values()) > 400  # the gather's and the scatter's few beside them
    assert any("transpose(jvp(wm_rssm))" in v for v in tf_ops.values())  # the backward of a part keeps its name
    cap = capture_for(XL_CELL, trace_reduce.read_planes(SCOPES))
    by_part = cap.part_seconds()
    assert cap.scoped and set(by_part) == set(cap.step_parts) | {None} and len(cap.step_parts) == 8
    assert by_part == pytest.approx({(None if k == "None" else k): v for k, v in WANT["part_seconds"].items()}, rel=1e-9)
    # wrappers hold the ops of a body, so none is booked; every booked op ran inside an execution of jit_train
    assert all(n.split(".", 1)[0] not in trace_reduce.WRAPPERS for _, n, _ in cap.train_ops)
    runs = trace_reduce.reduce_file(SCOPES)["programs"]["jit_train"]
    assert sum(by_part.values()) <= runs["seconds"] and runs["executions"] == WANT["grad_steps"]
    assert max(by_part, key=by_part.get) == "wm_rssm"


def test_host_spans_keep_their_thread_and_their_counts():
    cap = capture_for(XL_CELL, trace_reduce.read_planes(SCOPES))
    learner = cap.learner_thread()
    threads = {th for _, th, *_ in cap.host}
    assert len(threads) == 2 and learner in threads  # two lines, both named python3
    assert {n for n, th, *_ in cap.host if th != learner} == {
        "Time/env_interaction_time", "Wait/player_queue", "Player/act", "Player/env_step", "Player/record"}
    refresh = cap.spans("Time/param_refresh")
    assert refresh and all(st["bytes"] == 822949460 and st["leaves"] == 125 for *_, st in refresh)
    assert all("grad_steps" in st and "burst" in st for *_, st in cap.spans("Time/train_time"))
    assert cap.instrumented and not capture_for(XL_CELL, trace_reduce.read_planes(PARENT)).instrumented
    # the accepted reducer still finds its two spans under their bare names, counts and all
    assert {"Time/train_time", "Time/env_interaction_time"} <= set(trace_reduce.reduce_file(SCOPES)["spans_s"])


def test_trim_scopes_reads_back_what_it_wrote():
    from perfbench import trim_scopes

    # (a reader added since that finds nothing to read in a DreamerV3 capture is left out, as in a run's line)
    assert trim_scopes.readings(SCOPES, spec_and_adapter(XL_CELL)[1])["metrics"] == pytest.approx(WANT["metrics"], rel=1e-9, abs=1e-12)


# -- the step's parts are the cell's adapter's (PR 37): no list of one algorithm's scopes is in `span_reduce` --
OTHER_PARTS = ("embed", "attention", "experts", "head", "optimizer")


@pytest.mark.parametrize("op_name,part", [
    ("jit(update)/while/body/jvp(attention)/dot_general", "attention"),
    ("jit(update)/while/body/transpose(jvp(experts))/while/body/mul", "experts"),
    ("jit(update)/jvp(attention)/experts/router/top_k", "experts"),     # the innermost part
    ("jit(update)/optimizer/sub", "optimizer"),
    ("jit(update)/Attention_0/attention_out/experts_like/add", None),   # no whole component
    ("jit(update)/transpose(jvp(wm_rssm))/while/body/mul", None),        # another step's part is none of this step's
    ("", None),
])
def test_part_of_books_an_op_by_the_parts_it_is_handed(op_name, part):
    assert span_reduce.part_of(op_name, OTHER_PARTS) == part
    assert span_reduce.part_of(op_name, ()) is None  # a step without scopes books nothing


def test_part_of_reads_the_recorded_steps_op_names_by_its_adapters_parts_as_before():
    parts = spec_and_adapter(XL_CELL)[1].step_parts
    assert span_reduce.part_of("jit(train)/while/body/closed_call/transpose(jvp(wm_rssm))/while/body/mul", parts) == "wm_rssm"
    assert span_reduce.part_of("jit(train)/while/body/jvp(actor)/imagination/while/body/WorldModel.imagination/dot_general", parts) == "imagination"
    assert span_reduce.part_of("jit(train)/while/body/Actor_0/actor_head/critic_like/add", parts) is None
    booked = {span_reduce.part_of(v, parts) for v in span_reduce.read_tf_ops(SCOPES).values()}
    assert booked == set(parts) | {None}  # every part of the step has an op in the recorded capture


def synthetic_planes(ops):
    """One device plane: three whole executions of `jit_update` in a 10 us window, `ops` (name, start, end in ns) in the
    first, one op before the window; no file behind it, so the ops' `tf_op` comes from the test."""
    mark = lambda name, t: (name, "t#0", t, t, {})  # noqa: E731
    host = [mark(trace_reduce.OPEN_MARK, 0.0), mark(trace_reduce.CLOSE_MARK, 10_000.0), ("Time/train_time", "t#0", 100.0, 200.0, {})]
    modules = [("jit_update(1)", 1000.0, 2000.0), ("jit_update(1)", 4000.0, 5000.0), ("jit_update(1)", 7000.0, 8000.0),
               ("jit_other(2)", 8500.0, 9000.0)]
    return {"host": host, "devices": {"/device:TPU:0": {"modules": modules, "ops": list(ops)}}, "path": None}


def test_capture_books_a_synthetic_op_list_by_another_adapters_parts(monkeypatch):
    ops = [("%fusion.1 = f32[8]{0} fusion(...)", 1000.0, 1400.0), ("%fusion.2 = f32[8]{0} fusion(...)", 1400.0, 1700.0),
           ("%copy.3 = f32[8]{0} copy(...)", 1700.0, 1800.0), ("%fusion.4 = f32[8]{0} fusion(...)", 4000.0, 4500.0),
           ("%fusion.9 = f32[8]{0} fusion(...)", 8500.0, 8900.0)]  # the last runs in another program
    tf_ops = {ops[0][0]: "jit(update)/jvp(attention)/dot_general:", ops[1][0]: "jit(update)/transpose(jvp(experts))/mul:",
              ops[2][0]: "jit(update)/copy:", ops[3][0]: "jit(update)/jvp(attention)/experts/top_k:", ops[4][0]: "jit(other)/jvp(attention)/x:"}
    planes = dict(synthetic_planes(ops), path="synthetic.xplane.pb")
    monkeypatch.setattr(span_reduce, "read_tf_ops", lambda path, prefer: dict(tf_ops, prefer=prefer))
    cap = span_reduce.Capture(planes, ("jit_update",), OTHER_PARTS)
    assert cap.step_parts == OTHER_PARTS and cap.step_executions == 3 and cap.scoped
    assert cap.part_seconds() == pytest.approx({"attention": 400e-9, "experts": 800e-9, None: 100e-9})
    ctx = {"capture": cap, "window": {"grad_steps": 3, "train_calls": 3}}
    assert span_reduce.part_ms(ctx, "attention") == pytest.approx(1e3 * 400e-9 / 3)
    assert span_reduce.part_ms(ctx, "head") == 0.0            # a part of this step with no op in the window
    assert span_reduce.part_ms(ctx, "wm_rssm") is None        # no part of this step: nothing to read, never 0
    assert span_reduce.unscoped_pct(ctx) == pytest.approx(100 * 100 / 1300)
    assert metric_reader("train_step.wm_rssm_ms")(ctx) is None and metric_reader("train_step.unscoped_pct")(ctx) == pytest.approx(100 / 13)
    # the same capture read by a step that names no part: every op unscoped, and the share is nothing to read
    bare = span_reduce.Capture(planes, ("jit_update",), ())
    assert not bare.scoped and set(bare.part_seconds()) == {None}
    assert span_reduce.unscoped_pct({"capture": bare}) is None and span_reduce.part_ms({**ctx, "capture": bare}, "attention") is None


def test_the_recorded_capture_read_by_another_steps_parts_books_nothing_of_dreamer_v3s():
    planes = trace_reduce.read_planes(SCOPES)
    adapter = spec_and_adapter(XL_CELL)[1]
    other = span_reduce.Capture(planes, adapter.step_programs, OTHER_PARTS)
    mine = capture_for(XL_CELL, planes)
    # only `optimizer` is a name of both: the rest of the step's op time falls to no part
    assert set(other.part_seconds()) == {"optimizer", None}
    assert other.part_seconds()["optimizer"] == pytest.approx(mine.part_seconds()["optimizer"])
    assert sum(other.part_seconds().values()) == pytest.approx(sum(mine.part_seconds().values()))
    # and by its own adapter's parts the per-part milliseconds are the recorded ones
    ctx = {"capture": mine, "window": {"grad_steps": WANT["grad_steps"], "train_calls": WANT["grad_steps"]}}
    for part in mine.step_parts:
        want = WANT["metrics"].get(f"train_step.{part}_ms")
        if want is not None:
            assert span_reduce.part_ms(ctx, part) == pytest.approx(want, rel=1e-9)
