"""The control (the reference with float8 operands put in the program's place)
and each planted fault have to fail at least one limit of the accepted cell;
the reference against itself has to pass every one. At the size of the tests'
own fixture; the readings the limits were set from were taken on the chip at
the cell's own size (PERF.md). Then the fixture's cell through the harness on
the CPU, sound. With each fault planted beneath the harness's wrappers it runs
in `test_pb_ppo_recurrent_sequence_control_faults.py`: a file of its own, so
that the minute-long harness processes spread over the workers; `_control` in
its name keeps it, like this file, out of `pb_rehearsal.py`'s rehearsal."""
import json

import numpy as np
import pytest

from pb_helpers import run_harness

SEQ_BENCH, SEQ_CELL = "tests/perfbench/fixtures/seq_bench.json", "xing4_tiny.gen4x32"


@pytest.fixture(scope="module")
def sides():
    import jax
    import jax.numpy as jnp

    from perfbench import check
    from perfbench.adapters import ppo_recurrent_sequence as adapter
    from perfbench.envs import reset_registry
    from perfbench.run import load_cell

    spec = load_cell(SEQ_CELL, SEQ_BENCH)
    cfg, shapes = adapter.program_shapes(spec)
    sz = adapter.sizes_for(cfg, 2, 2)
    seed = 2147483659
    reset_registry()
    rollout = adapter.rollout_from_generator(spec, seed, sz)
    coefs = {"clip_coef": 0.2, "ent_coef": 0.001, "vf_coef": 0.2, "lr_frac": 1.0}
    key = np.asarray(jax.random.key_data(jax.random.key(3)))
    make = lambda **kw: adapter.reference_side(seed, shapes, rollout, coefs, key, sz, **kw)  # noqa: E731
    ref = make()
    return {
        "limits": check.load_limits(load_cell("xing4_a4b.gen32x512")["limits_file"]),
        "same": adapter.compare_sides(ref, ref)[0],
        "control_fp8": adapter.compare_sides(make(od=jnp.float8_e4m3fn), ref)[0],
        **{f"fault_{kind}": adapter.compare_sides(make(fault=kind), ref)[0] for kind in adapter.fault_kinds},
    }


def failing(values, limits):
    return [k for k, lim in limits.items() if not values[k] <= lim]


def test_reference_against_itself_passes_every_limit(sides):
    assert failing(sides["same"], sides["limits"]) == [] and sides["same"]["routing_flips"] == 0.0


@pytest.mark.parametrize("side", ["control_fp8", "fault_unchanged", "fault_half_steps", "fault_half_batch"])
def test_control_and_fault_fail_a_limit(sides, side):
    from perfbench.adapters.ppo_recurrent_sequence import fault_kinds

    failed = failing(sides[side], sides["limits"])
    assert failed, sides[side]
    if side.startswith("fault_"):  # and one of the numbers the adapter names for the kind
        assert any(k.startswith(fault_kinds[side[len("fault_"):]]) for k in failed), failed


def test_parameters_left_unchanged_fail_the_updates_limits_alone(sides):
    unchanged = sides["fault_unchanged"]
    assert failing(unchanged, sides["limits"]) == ["update_gap", "update_mid"] and unchanged["update_gap"] == unchanged["update_mid"] == 1.0


@pytest.fixture(scope="module")
def sound():
    return run_harness("--benchmark", SEQ_BENCH, "--workload", SEQ_CELL, "--seed", "3000000023", "--seconds", "2", "--trace", "1", "--rehearse-cpu")


def test_the_fixtures_cell_runs_through_the_harness_and_is_correct(sound):
    from pb_checks import spec_and_adapter

    rc, out, err = sound
    assert rc == 0, err[-3000:]
    line = json.loads(out[-1])
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert "algo=ppo_recurrent" in err and "trace reduced" in err
    assert set(line["compared"]) <= spec_and_adapter(SEQ_CELL, SEQ_BENCH)[1].compared_numbers
    assert {"rollout_wrong_rows", "routing_flips", "logprobs_gap", "values_gap", "update_gap", "moe_dropped"} <= set(line["compared"])
    assert set(line["metrics"]) <= {"setup_s"}  # a CPU run carries no device metric
