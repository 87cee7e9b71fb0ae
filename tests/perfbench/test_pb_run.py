"""The harness end to end on the CPU at tiny widths: the result line, the
no-chip exit, and that a rehearsal never carries a device metric."""
import json

import pytest

from pb_helpers import CELLS, run_harness

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def rehearsal():
    rc, out, err = run_harness("--workload", CELLS[0], "--seed", "3000000019", "--seconds", "1", "--trace", "1", "--rehearse-cpu")
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
    assert reads["replay_wrong_rows"] == 0.0


def test_rehearsal_leaves_nothing_in_the_checkout(rehearsal):
    import os

    from pb_helpers import ROOT

    assert not any(n.startswith("perfbench_") for n in os.listdir(ROOT))
    assert not os.path.isdir(os.path.join(ROOT, "logs", "runs", "dreamer_v3", "perfbench_crafter"))


def test_a_run_that_finds_no_tpu_exits_nonzero_and_prints_no_result():
    rc, out, err = run_harness("--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0", timeout=300)
    assert rc != 0 and out == [] and "no result" in err


def test_an_unknown_workload_is_an_error():
    rc, out, _ = run_harness("--workload", "no.such", "--seed", "1", "--seconds", "1", "--trace", "0", timeout=120)
    assert rc != 0 and out == []
