"""The timed path broken underneath the harness: `correct` has to come out
false, once for each fault a training cell can have. (The exchange between
chips has no place in a one-chip cell.)"""
import json

import pytest

from pb_checks import spec_and_adapter
from pb_helpers import L_CELL, XL_CELL, run_harness

# the faults the cell's adapter plants, each with the numbers of which one has to fail
FAILS = spec_and_adapter(L_CELL)[1].fault_kinds


@pytest.mark.parametrize("fault", list(FAILS))
def test_fault_makes_the_run_incorrect(fault):
    rc, out, err = run_harness("--workload", L_CELL, "--seed", "2147483659", "--seconds", "1", "--trace", "0",
                               "--rehearse-cpu", "--fault", fault)
    assert rc == 0, err[-3000:]
    line = json.loads(out[-1])
    assert line["correct"] is False
    failed = [k for k, c in line["compared"].items() if not c["value"] <= c["limit"]]
    assert any(k.startswith(FAILS[fault]) for k in failed), (fault, failed)
    assert "<-- FAILS" in err


def test_the_programs_own_lower_precision_path_comes_out_incorrect():
    """The program at `fabric.precision=bf16-mixed` in the place of the
    `32-true` the configuration states. On the CPU, where `32-true` multiplies
    in float32, that is a step down and the limits see it. On the chip it is
    none (the default matmul precision rounds operands to bfloat16 already)
    and it reads as sound runs do: PERF.md section 6."""
    rc, out, err = run_harness("--workload", XL_CELL, "--seed", "3000000021", "--seconds", "1", "--trace", "0",
                               "--rehearse-cpu", "--control", "bf16-mixed")
    assert rc == 0, err[-3000:]
    assert json.loads(out[-1])["correct"] is False and "<-- FAILS" in err
