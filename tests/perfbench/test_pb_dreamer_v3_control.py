"""The control (the reference with float8 operands put in the program's place)
and the planted faults have to fail at least one limit; the reference against
itself has to pass every one. At a size a test run can hold; the readings the
limits were set from were taken on the chip at the cells' own sizes (PERF.md)."""
import numpy as np
import pytest


@pytest.fixture(scope="module")
def sides():
    import jax
    import jax.numpy as jnp

    from perfbench import check
    from perfbench.adapters import dreamer_v3 as dv3
    from perfbench.run import load_cell

    spec = load_cell("dv3_l.navigate4")
    cfg, shapes = dv3.program_shapes(spec, rehearse=True)
    sz = dv3.sizes_for(cfg, spec["mix"])
    seed = 2147483659
    batches = dv3.batches_from_generator("navigate4", spec["mix"], seed, 8, 4, 200, dv3.CHECK_STEPS)
    keys = [np.asarray(jax.random.key_data(jax.random.split(jax.random.key(i), 1)))[0] for i in range(dv3.CHECK_STEPS)]
    ref = dv3.reference_side(seed, shapes, batches, keys, sz)
    make = lambda **kw: dv3.compare_sides(dv3.reference_side(seed, shapes, batches, keys, sz, **kw), ref)[0]  # noqa: E731
    return {
        "limits": check.load_limits(spec["limits_file"]),
        "same": dv3.compare_sides(ref, ref)[0],
        "control_fp8": make(od=jnp.float8_e4m3fn),
        "fault_half_batch": make(faults=("half_batch",)),
        "fault_unchanged": make(faults=("unchanged",)),
        "fault_unchanged_actor": make(faults=("unchanged_actor",)),
    }


def failing(values, limits):
    return [k for k, lim in limits.items() if not values[k] <= lim]


def test_reference_against_itself_passes_every_limit(sides):
    assert failing(sides["same"], sides["limits"]) == []


@pytest.mark.parametrize("side", ["control_fp8", "fault_half_batch", "fault_unchanged", "fault_unchanged_actor"])
def test_control_and_faults_fail_a_limit(sides, side):
    assert failing(sides[side], sides["limits"]), sides[side]


def test_an_actor_left_unchanged_fails_the_actors_limit_alone(sides):
    assert failing(sides["fault_unchanged_actor"], sides["limits"]) == ["update_gap_actor"]


def test_generator_batches_are_what_the_replay_check_accepts():
    """The rows the calibration builds from the generator alone are rows the
    run-time comparison would accept from the ring."""
    from perfbench import check
    from perfbench.adapters import dreamer_v3 as dv3
    from perfbench.envs import REGISTRY, load_mix, reset_registry

    reset_registry()
    mix = load_mix("navigate4")
    batches = dv3.batches_from_generator("navigate4", mix, 41, 8, 4, 300, 2)
    rows, wrong, broken = check.replay_rows(batches, dict(REGISTRY), "rgb")
    assert rows == 2 * 8 * 4 and wrong == 0 and broken == 0
