"""The harness end to end on the CPU at tiny widths: the result line, the
no-chip exit, and that a rehearsal never carries a device metric."""
import json

import pytest

from pb_checks import spec_and_adapter
from pb_helpers import PPO_BENCH, PPO_CELL, XL_CELL, run_harness

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def rehearsal(xl_rehearsal):
    rc, out, err, _ = xl_rehearsal
    return rc, out, err


def test_rehearsal_exits_zero_and_its_last_line_has_the_contracts_keys(rehearsal):
    rc, out, err = rehearsal
    assert rc == 0, err[-3000:]
    line = json.loads(out[-1])
    assert list(line)[: len(CONTRACT_KEYS)] == CONTRACT_KEYS and list(line)[-1] == "compared"
    assert set(line) == set(CONTRACT_KEYS) | {"compared"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert all(set(c) == {"value", "limit"} for c in line["compared"].values())
    assert {"replay_wrong_rows", "ratio_early_steps", "ratio_late_steps"} <= set(line["compared"])


def test_rehearsal_names_its_device_on_an_earlier_line_and_prints_no_device_metric(rehearsal):
    _, out, err = rehearsal
    assert "platform=cpu device_kind='cpu' count=" in err
    line = json.loads(out[-1])
    assert line["device"]["platform"] == "cpu" and "busy_s" not in line["device"] and "breakdown" not in line
    assert set(line["metrics"]) <= {"setup_s"}  # no rate, time, utilization or roofline share from a CPU run


def test_rehearsal_agrees_with_the_reference_to_float32_rounding(rehearsal):
    """At tiny widths on the CPU the program multiplies in float32, so the
    plain reference has to reproduce it: every compared gap is far under its limit."""
    _, out, err = rehearsal
    reads = {ln.split()[1]: float(ln.split()[3]) for ln in err.splitlines() if ln.startswith(("[compared]", "[read]"))}
    gaps = {k: v for k, v in reads.items() if "_gap_" in k or "_mid_" in k}
    assert len(gaps) == 18 and max(gaps.values()) < 2e-3, gaps
    # every number the program's `decide` returned, with a limit or without, is one its adapter names
    assert set(reads) <= spec_and_adapter(XL_CELL)[1].compared_numbers, set(reads)
    assert reads["replay_wrong_rows"] == 0.0


def test_rehearsal_leaves_nothing_in_the_checkout(rehearsal):
    import os

    from pb_helpers import ROOT

    assert not any(n.startswith("perfbench_") for n in os.listdir(ROOT))
    assert not os.path.isdir(os.path.join(ROOT, "logs", "runs", "dreamer_v3", "perfbench_crafter"))


def test_a_run_that_finds_no_tpu_exits_nonzero_and_prints_no_result():
    rc, out, err = run_harness("--workload", XL_CELL, "--seed", "1", "--seconds", "1", "--trace", "0", timeout=300)
    assert rc != 0 and out == [] and "no result" in err


def test_an_unknown_workload_is_an_error():
    rc, out, _ = run_harness("--workload", "no.such", "--seed", "1", "--seconds", "1", "--trace", "0", timeout=120)
    assert rc != 0 and out == []


# -- a second algorithm through the same harness: `exp=ppo` through `cli.run`, as files alone --
@pytest.fixture(scope="module")
def ppo_rehearsal():
    return run_harness("--benchmark", PPO_BENCH, "--workload", PPO_CELL, "--seed", "1", "--seconds", "2", "--trace", "1", "--rehearse-cpu")


def test_a_cell_of_another_algorithm_runs_through_the_seam_and_is_correct(ppo_rehearsal):
    rc, out, err = ppo_rehearsal
    assert rc == 0, err[-3000:]
    line = json.loads(out[-1])
    assert list(line)[: len(CONTRACT_KEYS)] == CONTRACT_KEYS and list(line)[-1] == "compared"
    assert line["correct"] is True and line["attempted"] > 0
    from perfbench.check import load_limits

    spec, adapter = spec_and_adapter(PPO_CELL, PPO_BENCH)
    assert set(load_limits(spec["limits_file"])) < set(line["compared"]) <= adapter.compared_numbers  # the file's and the structural
    assert "algo=ppo" in err and "weights from the seed: 18 leaves" in err


def test_the_other_algorithms_window_is_the_same_window(ppo_rehearsal):
    """One update a rollout of 16 steps x 2 envs, G = 1 epoch x 1 minibatch: the stamps, the counts and the capture agree."""
    _, _, err = ppo_rehearsal
    window = json.loads(next(ln for ln in err.splitlines() if "s window {" in ln).split("window ", 1)[1])
    assert window["grad_steps"] == window["train_calls"] > 3 and (window["minibatches"], window["minibatch_rows"]) == (1, 32)
    assert abs(window["env_steps"] - 32 * window["train_calls"]) <= 32 and 2.0 <= window["window_s"] < 4.0
    assert "window opens after 3 train calls" in err and "trace written" in err and "trace reduced" in err


def test_the_other_algorithm_agrees_with_its_reference_to_float32_rounding(ppo_rehearsal):
    _, out, _ = ppo_rehearsal
    gaps = {k: c["value"] for k, c in json.loads(out[-1])["compared"].items() if "_gap" in k}
    assert len(gaps) == 7 and max(gaps.values()) < 1e-4, gaps


def test_parameters_left_unchanged_make_the_other_algorithms_run_incorrect():
    rc, out, err = run_harness("--benchmark", PPO_BENCH, "--workload", PPO_CELL, "--seed", "3000000019", "--seconds", "2",
                               "--trace", "0", "--rehearse-cpu", "--fault", "unchanged")
    assert rc == 0, err[-3000:]
    line = json.loads(out[-1])
    failed = [k for k, c in line["compared"].items() if not c["value"] <= c["limit"]]
    assert line["correct"] is False and failed == ["update_gap"] and line["compared"]["update_gap"]["value"] == 1.0
    assert "<-- FAILS" in err


def test_a_cell_of_the_fixture_is_not_in_the_benchmark_and_the_default_file_is_the_roots():
    rc, out, _ = run_harness("--workload", PPO_CELL, "--seed", "1", "--seconds", "1", "--trace", "0", timeout=120)
    assert rc != 0 and out == []
