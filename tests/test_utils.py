"""Unit tests for the core host-side utilities: Ratio (replay-ratio
controller, reference sheeprl/utils/utils.py:259-300), MetricAggregator /
RunningMetric (reference metric.py), the timer registry (reference
timer.py), and MaskVelocityWrapper (reference wrappers.py:13-45)."""
import time

import gymnasium as gym
import numpy as np
import pytest

from sheeprl_tpu.envs.wrappers import MaskVelocityWrapper
from sheeprl_tpu.utils.metric import MetricAggregator, RunningMetric
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio


# ---------------------------------------------------------------- Ratio ----
def test_ratio_first_call_returns_pretrain_budget():
    r = Ratio(0.5, pretrain_steps=10)
    assert r(100) == 5  # 10 * 0.5, regardless of step
    # afterwards: proportional to step delta
    assert r(104) == 2


def test_ratio_pretrain_rounds_up_to_one():
    r = Ratio(0.01, pretrain_steps=10)  # 10 * 0.01 = 0.1 → at least 1
    assert r(0) == 1


def test_ratio_accumulates_fractional_budget():
    r = Ratio(0.0625)  # one gradient step per 16 env steps
    r(0)  # anchors _prev
    got = [r(s) for s in range(1, 65)]
    assert sum(got) == 4  # 64 * 0.0625
    assert max(got) == 1  # never bursts


def test_ratio_zero_is_inert():
    r = Ratio(0.0)
    assert r(0) == 0 and r(1000) == 0 and r.peek(5000) == 0


def test_ratio_peek_matches_call():
    r = Ratio(0.3)
    r(0)
    for step in (7, 20, 21, 50):
        expected = r.peek(step)
        assert r(step) == expected


def test_ratio_state_dict_round_trip():
    r = Ratio(0.25, pretrain_steps=4)
    r(0)
    r(10)
    r2 = Ratio(1.0).load_state_dict(r.state_dict())
    assert r2._ratio == 0.25
    assert r2._prev == r._prev
    assert r2(20) == r.peek(20)  # restored controller predicts like the original


def test_ratio_validates_args():
    with pytest.raises(ValueError):
        Ratio(-1.0)
    with pytest.raises(ValueError):
        Ratio(0.5, pretrain_steps=-1)


# -------------------------------------------------------------- metrics ----
def test_running_metric_kinds():
    m = RunningMetric("mean")
    m.update([1.0, 3.0])
    m.update(5.0)
    assert m.compute() == pytest.approx(3.0)
    s = RunningMetric("sum")
    s.update([1.0, 2.0])
    s.update(4.0)
    assert s.compute() == pytest.approx(7.0)
    mx = RunningMetric("max")
    mx.update([1.0, 9.0])
    mx.update(4.0)
    assert mx.compute() == 9.0
    last = RunningMetric("last")
    last.update(1.0)
    last.update(2.0)
    assert last.compute() == 2.0


def test_running_metric_empty_returns_none():
    assert RunningMetric("mean").compute() is None
    assert RunningMetric("sum").compute() is None
    assert RunningMetric("max").compute() is None


def test_aggregator_whitelist_and_nan_filtering(monkeypatch):
    # the class-level kill switch is set by cli.run from metric.log_level, so
    # a preceding e2e test with log_level=0 would otherwise leak True in here
    monkeypatch.setattr(MetricAggregator, "disabled", False)
    agg = MetricAggregator({"Loss/a": {"kind": "mean"}, "Loss/b": {"kind": "sum"}})
    agg.update("Loss/a", 2.0)
    agg.update("Loss/a", np.nan)  # NaN aggregate is dropped at compute
    agg.update("Loss/b", 3.0)
    agg.update("Loss/unknown", 1.0)  # not registered → ignored
    out = agg.compute()
    assert "Loss/a" not in out  # poisoned by NaN → filtered (reference metric.py NaN filter)
    assert out.get("Loss/b") == pytest.approx(3.0)
    assert "Loss/unknown" not in out
    agg.reset()
    agg.update("Loss/a", 4.0)  # reset clears the poison
    assert agg.compute().get("Loss/a") == pytest.approx(4.0)


def test_aggregator_disabled_switch():
    agg = MetricAggregator({"x": {"kind": "mean"}})
    MetricAggregator.disabled = True
    try:
        agg.update("x", 1.0)
        assert not agg.compute()
    finally:
        MetricAggregator.disabled = False


# ---------------------------------------------------------------- timer ----
def test_timer_accumulates_and_resets():
    timer.reset()
    with timer("Time/unit_test"):
        time.sleep(0.01)
    with timer("Time/unit_test"):
        time.sleep(0.01)
    total = timer.compute()["Time/unit_test"]
    assert total >= 0.02
    timer.reset()
    assert "Time/unit_test" not in timer.compute()


def test_timer_disabled_records_nothing():
    timer.reset()
    timer.disabled = True
    try:
        with timer("Time/off"):
            time.sleep(0.005)
        assert "Time/off" not in timer.compute()
    finally:
        timer.disabled = False


# -------------------------------------------------------- MaskVelocity ----
def test_mask_velocity_zeroes_velocity_entries():
    env = MaskVelocityWrapper(gym.make("CartPole-v1"))
    obs, _ = env.reset(seed=0)
    assert obs[1] == 0.0 and obs[3] == 0.0  # velocities masked
    obs2, *_ = env.step(env.action_space.sample())
    assert obs2[1] == 0.0 and obs2[3] == 0.0
    assert obs2[0] != 0.0 or obs2[2] != 0.0  # positions untouched
    env.close()


def test_mask_velocity_unknown_env_raises():
    class _NoSpec(gym.Env):
        observation_space = gym.spaces.Box(-1, 1, (4,))
        action_space = gym.spaces.Discrete(2)

    with pytest.raises(NotImplementedError):
        MaskVelocityWrapper(_NoSpec())


def test_every_algorithm_has_an_evaluation():
    """Parity guarantee of the reference's per-algo evaluate.py files: every
    registered training entry point must be evaluable from a checkpoint
    (`eval` on any algo.name resolves; VERDICT r3 item 4 regression)."""
    import sheeprl_tpu  # noqa: F401 — populates both registries
    from sheeprl_tpu.utils.registry import algorithm_registry, evaluation_registry

    missing = sorted(set(algorithm_registry) - set(evaluation_registry))
    assert not missing, f"algorithms without a registered evaluation: {missing}"
    assert len(algorithm_registry) >= 17, (
        f"reference parity needs all 17 entry points; got {sorted(algorithm_registry)}"
    )


# ------------------------------------------------- compilation cache dir ----
def _with_restored_cache_dir(fn):
    import jax

    before = jax.config.jax_compilation_cache_dir
    try:
        return fn(jax)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compilation_cache_default_is_a_fixed_path_in_the_checkout(monkeypatch):
    """The directory is part of every cache key, so the default must not
    depend on $HOME or the cwd: <checkout>/.xla_cache (git-ignored)."""
    import os

    from sheeprl_tpu.utils.utils import DEFAULT_XLA_CACHE_DIR, enable_compilation_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("SHEEPRL_NO_COMPILATION_CACHE", raising=False)
    monkeypatch.setenv("HOME", "/nonexistent-home")
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert DEFAULT_XLA_CACHE_DIR == os.path.join(checkout, ".xla_cache")

    def check(jax):
        jax.config.update("jax_compilation_cache_dir", None)
        enable_compilation_cache()
        assert jax.config.jax_compilation_cache_dir == DEFAULT_XLA_CACHE_DIR

    _with_restored_cache_dir(check)


def test_compilation_cache_dir_from_the_environment_wins(monkeypatch, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, JAX already caches there (it
    reads the variable itself) and the program sets no other directory."""
    from sheeprl_tpu.utils.utils import enable_compilation_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed-from-outside"))
    monkeypatch.delenv("SHEEPRL_NO_COMPILATION_CACHE", raising=False)

    def check(jax):
        jax.config.update("jax_compilation_cache_dir", "what-jax-read-from-the-environment")
        enable_compilation_cache()
        assert jax.config.jax_compilation_cache_dir == "what-jax-read-from-the-environment"

    _with_restored_cache_dir(check)


@pytest.mark.parametrize("platforms, keyed", [("tpu,cpu", True), (None, True), ("cpu", False)])
def test_compilation_cache_keys_on_op_metadata_unless_held_to_the_cpu(monkeypatch, platforms, keyed):
    """A cached executable keeps the `jax.named_scope` names it was compiled
    with, so where a capture's op names are read the key has to hold them."""
    from sheeprl_tpu.utils.utils import enable_compilation_cache

    monkeypatch.delenv("SHEEPRL_NO_COMPILATION_CACHE", raising=False)

    def check(jax):
        was = jax.config.jax_platforms, jax.config.jax_compilation_cache_include_metadata_in_key
        try:
            jax.config.update("jax_platforms", platforms)
            jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
            enable_compilation_cache()
            assert jax.config.jax_compilation_cache_include_metadata_in_key is keyed
        finally:
            jax.config.update("jax_platforms", was[0])
            jax.config.update("jax_compilation_cache_include_metadata_in_key", was[1])

    _with_restored_cache_dir(check)
