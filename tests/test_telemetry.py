"""Unified telemetry subsystem tests: span nesting/drain, the legacy timer
shim, retrace detection with shape attribution, JSONL schema round-trip,
startup heartbeat, registry-wide StepTraceAnnotation installation, the
TensorBoard fallback sink, and a short end-to-end CPU PPO smoke run whose
emitted event stream is validated against the schema (the tier-1 CI gate for
the telemetry contract)."""
import glob
import inspect
import json
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from sheeprl_tpu.telemetry import (
    JsonlSink,
    RetraceDetector,
    Span,
    SpanTracker,
    Telemetry,
    mfu,
    validate_event,
    validate_jsonl,
    write_event,
)
from sheeprl_tpu.telemetry.throughput import ThroughputTracker
from sheeprl_tpu.utils.timer import timer


# -- spans ------------------------------------------------------------------


def test_span_nesting_records_both_and_child_leq_parent():
    tracker = SpanTracker()
    with Span("outer", tracker=tracker):
        assert tracker.current() == "outer"
        with Span("outer/inner", tracker=tracker):
            assert tracker.current() == "outer/inner"
            assert tracker.depth() == 2
            time.sleep(0.01)
    totals = tracker.compute()
    assert set(totals) == {"outer", "outer/inner"}
    assert 0 < totals["outer/inner"] <= totals["outer"]
    assert tracker.counts() == {"outer": 1, "outer/inner": 1}


def test_span_drain_semantics():
    tracker = SpanTracker()
    with Span("a", tracker=tracker):
        pass
    first = tracker.compute(reset=True)
    assert "a" in first
    assert tracker.compute() == {}  # drained
    with Span("a", tracker=tracker):
        pass
    second = tracker.compute(reset=True)
    # no double counting: the second interval only holds the second span
    assert second["a"] < first["a"] + second["a"]


def test_timer_shim_accumulates_and_drains():
    timer.reset()
    with timer("Time/x"):
        pass
    with timer("Time/x"):
        pass
    totals = timer.compute(reset=True)
    assert totals["Time/x"] > 0
    assert timer.compute() == {}


def test_timer_shim_thread_safe():
    timer.reset()
    stop = threading.Event()

    def spin(name):
        while not stop.is_set():
            with timer(name):
                pass

    threads = [threading.Thread(target=spin, args=(f"Time/t{i}",)) for i in range(2)]
    for t in threads:
        t.start()
    time.sleep(0.05)
    # concurrent drain while both threads keep recording must not lose or
    # corrupt entries (the old class-dict implementation raced here)
    for _ in range(10):
        timer.compute(reset=True)
    stop.set()
    for t in threads:
        t.join()
    timer.reset()


def test_timer_disabled_records_nothing():
    timer.reset()
    timer.disabled = True
    try:
        with timer("Time/off"):
            pass
        assert "Time/off" not in timer.compute()
    finally:
        timer.disabled = False
        timer.reset()


# -- retrace detector -------------------------------------------------------


def test_retrace_detector_fires_on_shape_change_with_attribution():
    det = RetraceDetector()

    def step(x, params):
        return x * params["w"]

    f = jax.jit(det.wrap(step, "train_step"))
    p4 = {"w": jnp.ones((4,))}
    f(jnp.ones((4,)), p4)
    f(jnp.ones((4,)), p4)  # cache hit: no retrace
    assert det.trace_count("train_step") == 1
    assert det.retrace_count("train_step") == 0  # stays at initial compile

    f(jnp.ones((8,)), {"w": jnp.ones((8,))})  # shape change → retrace
    assert det.retrace_count("train_step") == 1
    attribution = det.attribution("train_step")
    assert len(attribution) == 1
    assert "(4,)" in attribution[0] and "(8,)" in attribution[0]


def test_retrace_detector_dtype_change():
    det = RetraceDetector()
    f = jax.jit(det.wrap(lambda x: x + 1, "g"))
    f(jnp.ones((2,), jnp.float32))
    f(jnp.ones((2,), jnp.int32))
    assert det.retrace_count("g") == 1
    assert "float32" in det.attribution("g")[0]


# -- schema / sinks ---------------------------------------------------------


def test_jsonl_schema_roundtrip(tmp_path):
    path = tmp_path / "telemetry.jsonl"
    sink = JsonlSink(str(path))
    sink.write({"event": "startup", "platform": "cpu", "device_kind": "cpu", "devices": 1, "rank": 0})
    sink.write({"event": "log", "step": 128, "sps": 42.0, "metrics": {}, "spans": {}, "xla": {}, "memory": {}})
    sink.write({"event": "shutdown", "step": 128})
    sink.close()
    assert validate_jsonl(path) == []
    events = [json.loads(line)["event"] for line in path.read_text().splitlines()]
    assert events == ["startup", "log", "shutdown"]


def test_validate_event_rejects_bad_records():
    assert validate_event({"no_event": 1})
    assert validate_event({"event": "nope"})
    assert validate_event({"event": "startup"})  # missing platform etc.
    assert validate_event({"event": "log", "step": "not a number"})
    assert validate_event({"event": "bench", "metric": "m"})  # missing value/unit/vs_baseline
    assert (
        validate_event(
            {"event": "bench", "metric": "m", "value": 1.0, "unit": "steps/s", "vs_baseline": 0.5}
        )
        == []
    )


def test_write_event_strict_raises(tmp_path):
    with pytest.raises(ValueError):
        write_event({"event": "startup"}, sys.stderr, strict=True)


def test_tensorboard_logger_fallback_to_jsonl(tmp_path, monkeypatch):
    # blocking both SummaryWriter backends must yield a warning, an
    # .available=False logger, and metrics landing in the JSONL fallback
    monkeypatch.setitem(sys.modules, "torch.utils.tensorboard", None)
    monkeypatch.setitem(sys.modules, "tensorboardX", None)
    import sheeprl_tpu.utils.logger as logger_mod

    monkeypatch.setattr(logger_mod, "_tb_import_warned", False)
    with pytest.warns(RuntimeWarning, match="SummaryWriter"):
        tb = logger_mod.TensorBoardLogger(str(tmp_path))
    assert not tb.available
    tb.log_metrics({"Loss/x": 1.5, "skipme": "not a number"}, step=7)
    tb.close()
    fallback = tmp_path / "metrics_fallback.jsonl"
    assert fallback.is_file()
    assert validate_jsonl(fallback) == []
    rec = json.loads(fallback.read_text().splitlines()[0])
    assert rec == {"event": "metrics", "step": 7, "metrics": {"Loss/x": 1.5}}


def test_tensorboard_logger_available_when_backend_present(tmp_path):
    import sheeprl_tpu.utils.logger as logger_mod

    tb = logger_mod.TensorBoardLogger(str(tmp_path))
    assert tb.available  # torch tensorboard is installed in the test image
    tb.close()


# -- throughput -------------------------------------------------------------


def test_throughput_tracker_and_mfu():
    tracker = ThroughputTracker(start_step=0)
    tracker.record_grad_steps(4)
    out = tracker.mark(16)
    assert out["interval_steps"] == 16
    assert out["replay_ratio"] == pytest.approx(4 / 16)
    assert out["sps"] > 0
    # mfu: whole-mesh flops*sps over per-chip peak * n_dev
    assert mfu(2e12, 1.0, 1e12, 2) == pytest.approx(1.0)


# -- facade -----------------------------------------------------------------


def test_heartbeat_prints_platform(tmp_path, capfd):
    telem = Telemetry(None, str(tmp_path), rank=0)
    telem.close()
    err = capfd.readouterr().err
    assert "[telemetry rank=0]" in err
    assert "platform=cpu" in err


def test_facade_tick_rotates_step_annotation(tmp_path, monkeypatch):
    entered = []

    class FakeAnnotation:
        def __init__(self, name, step_num=None, **kw):
            self.step_num = step_num

        def __enter__(self):
            entered.append(("enter", self.step_num))
            return self

        def __exit__(self, *exc):
            entered.append(("exit", self.step_num))
            return False

    import jax.profiler as prof

    monkeypatch.setattr(prof, "StepTraceAnnotation", FakeAnnotation)
    telem = Telemetry(None, str(tmp_path), rank=0)
    telem.tick(0)
    telem.tick(4)
    telem.close(4)
    assert entered == [("enter", 0), ("exit", 0), ("enter", 4), ("exit", 4)]


def test_facade_windowed_trace_capture(tmp_path, monkeypatch):
    calls = []
    import jax.profiler as prof

    monkeypatch.setattr(prof, "start_trace", lambda d: calls.append(("start", d)))
    monkeypatch.setattr(prof, "stop_trace", lambda: calls.append(("stop", None)))

    class Cfg:
        def select(self, path, default=None):
            return {
                "metric.telemetry.trace_every": 100,
                "metric.telemetry.trace_window": 10,
                "metric.telemetry.jsonl": False,
                "metric.telemetry.heartbeat": False,
            }.get(path, default)

    telem = Telemetry(Cfg(), str(tmp_path), rank=0)
    telem.tick(0)  # below trace_every since step 0 baseline: no capture yet
    telem.tick(100)  # crosses trace_every → start
    telem.tick(105)  # inside window
    telem.tick(112)  # window elapsed → stop
    telem.close(112)
    assert [c[0] for c in calls] == ["start", "stop"]
    assert calls[0][1].endswith("xprof")


def test_facade_honors_disable_timer(tmp_path):
    class Cfg:
        def select(self, path, default=None):
            return {
                "metric.disable_timer": True,
                "metric.telemetry.jsonl": False,
                "metric.telemetry.heartbeat": False,
            }.get(path, default)

    telem = Telemetry(Cfg(), str(tmp_path), rank=0)
    with telem.span("Time/train_time"):
        pass
    assert telem.tracker.compute() == {}  # benchmark configs strip span overhead
    telem.close()


def test_facade_log_record_schema(tmp_path, monkeypatch):
    # an earlier in-process cli run with log_level=0 leaves the class-level
    # kill switch on; this test exercises the enabled path
    from sheeprl_tpu.utils.metric import MetricAggregator

    monkeypatch.setattr(MetricAggregator, "disabled", False)
    telem = Telemetry(None, str(tmp_path), rank=0)
    telem.aggregator.add("Loss/x", "mean")
    telem.update("Loss/x", 2.0)
    with telem.span("Time/train_time"):
        pass
    telem.record_grad_steps(2)
    rec = telem.log(64)
    telem.close(64)
    assert validate_event(rec) == []
    assert rec["step"] == 64
    assert rec["metrics"]["Loss/x"] == pytest.approx(2.0)
    assert "Time/train_time" in rec["spans"]
    assert rec["throughput"]["replay_ratio"] == pytest.approx(2 / 64)
    assert validate_jsonl(tmp_path / "telemetry.jsonl") == []


def test_every_registered_algo_installs_step_annotation_and_facade():
    """Registry-driven: each of the 17 train entry points must tick the
    StepTraceAnnotation and set up the Telemetry facade."""
    import sheeprl_tpu  # populate the registry
    from sheeprl_tpu.utils.registry import algorithm_registry

    assert len(algorithm_registry) >= 17
    for name, info in sorted(algorithm_registry.items()):
        src = inspect.getsource(info["fn"])
        # the run around a loop may be a helper of the algorithm's own package that the entry point builds
        # (recurrent PPO's two backbones share `loop.LoopRun`): its source is the entry point's too
        helper = getattr(inspect.getmodule(info["fn"]), "LoopRun", None)
        if helper is not None and "LoopRun(" in src:
            src += inspect.getsource(helper)
        assert "telem.tick(" in src, f"{name}: no StepTraceAnnotation tick in train loop"
        assert "Telemetry.setup(" in src, f"{name}: train loop does not build the Telemetry facade"
        assert "telem.log(" in src, f"{name}: train loop does not flush telemetry log intervals"


# -- end-to-end smoke (the CI gate) ----------------------------------------


def test_ppo_smoke_emits_valid_jsonl(monkeypatch):
    """~32-step CPU PPO with telemetry on: the emitted JSONL stream must
    validate against the schema and contain the startup platform record,
    per-log-interval SPS, compile counts, device-memory stats and span
    timings (acceptance criteria of the telemetry subsystem)."""
    from sheeprl_tpu.cli import run

    # force real backend compiles so the compile counter moves even when the
    # persistent XLA cache is warm
    monkeypatch.setenv("SHEEPRL_NO_COMPILATION_CACHE", "1")
    run(
        [
            "exp=ppo",
            "env=dummy",
            "env.id=discrete_dummy",
            "env.num_envs=2",
            "env.sync_env=True",
            "env.capture_video=False",
            "algo.total_steps=32",
            "algo.rollout_steps=8",
            "algo.per_rank_batch_size=4",
            "algo.update_epochs=1",
            "algo.mlp_keys.encoder=[state]",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.encoder.cnn_features_dim=16",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.run_test=False",
            "metric.log_every=1",
            "metric.log_level=1",
            "buffer.memmap=False",
            "checkpoint.save_last=False",
        ]
    )
    streams = glob.glob("logs/runs/**/telemetry.jsonl", recursive=True)
    assert len(streams) == 1, f"expected one telemetry.jsonl, found {streams}"
    assert validate_jsonl(streams[0]) == []

    events = [json.loads(line) for line in open(streams[0])]
    by_type = {}
    for event in events:
        by_type.setdefault(event["event"], []).append(event)

    startup = by_type["startup"][0]
    assert startup["platform"] == "cpu"  # conftest forces the CPU backend
    assert startup["devices"] >= 1
    assert startup["algo"] == "ppo"

    logs = by_type["log"]
    assert len(logs) >= 2  # 32 steps / (8 rollout * 2 envs) iterations, log_every=1
    assert all(rec["sps"] > 0 for rec in logs)
    assert all("memory" in rec and "xla" in rec for rec in logs)
    # the jitted act/update fns compile inside the run window
    assert sum(rec["xla"]["compile_count"] for rec in logs[-1:]) >= 1
    spans = {name for rec in logs for name in rec["spans"]}
    assert "Time/env_interaction_time" in spans
    assert "Time/train_time" in spans

    shutdown = by_type["shutdown"][0]
    assert shutdown["step"] >= 32
    assert shutdown["total_grad_steps"] > 0

    # the learner's MemorySampler grows a host-RSS watermark series even on
    # the CPU backend (the closing sample is emitted on facade close)
    mems = by_type.get("mem", [])
    assert mems, "learner MemorySampler emitted no mem events"
    assert all(rec["role"] == "learner" and rec["rss_bytes"] > 0 for rec in mems)
    # the update fn registers its lowered cost → one roofline verdict
    rooflines = [rec for rec in by_type.get("roofline", []) if rec["fn"] == "train_step"]
    assert rooflines, "train loop did not register the update's roofline"
    assert rooflines[0]["intensity"] > 0
    assert rooflines[0]["bound"] in ("compute", "memory", "unknown")
