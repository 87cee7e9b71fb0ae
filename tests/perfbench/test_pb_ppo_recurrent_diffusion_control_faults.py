"""The timed path of the `ppo_recurrent_diffusion` adapter's fixture cell
broken beneath the harness's own wrappers (`adapter.faults`), once for each
fault the adapter plants: `correct` has to come out false, by one of the
numbers the adapter names for the kind. On the CPU at the fixture's size; the
readings at the accepted cell's own size were taken on the chip (PERF.md, the
limits file's `set_from`)."""
import json

import pytest

from pb_helpers import run_harness

BD_BENCH, BD_CELL = "tests/perfbench/fixtures/bd_bench.json", "sdar_tiny.blk4x8"


@pytest.mark.parametrize("fault", ["unchanged", "half_steps", "half_batch", "causal_block", "one_copy"])
def test_a_fault_beneath_the_harness_makes_the_run_incorrect(fault):
    from perfbench.adapters.ppo_recurrent_diffusion import fault_kinds

    rc, out, err = run_harness("--benchmark", BD_BENCH, "--workload", BD_CELL, "--seed", "2147483659", "--seconds", "1", "--trace", "0",
                               "--rehearse-cpu", "--fault", fault)
    assert rc == 0, err[-3000:]
    line = json.loads(out[-1])
    failed = [k for k, c in line["compared"].items() if not c["value"] <= c["limit"]]
    assert line["correct"] is False and "<-- FAILS" in err
    assert any(k.startswith(fault_kinds[fault]) for k in failed), failed
    if fault == "unchanged":  # every step scaled to nothing: the update's two limits alone
        assert set(failed) == {"update_gap", "update_mid"}
