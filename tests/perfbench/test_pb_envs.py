"""The traffic generator: seeded, stamped, and its own log is enough to rebuild any row."""
import inspect
import json
import os

import numpy as np
import pytest

import pb_checks
from pb_helpers import ANY_CELLS, ROOT, mix_files
from perfbench.envs import REGISTRY, SyntheticEnv, VectorEnv, generator_of, load_mix, reset_registry


@pytest.mark.parametrize("mix", ["crafter", "navigate4"])
def test_same_seed_same_frames_other_seed_other_frames(mix):
    a = SyntheticEnv(mix, bench_seed=3000000019, rank=0)
    b = SyntheticEnv(mix, bench_seed=3000000019, rank=0)
    c = SyntheticEnv(mix, bench_seed=3000000020, rank=0)
    oa, ob, oc = a.reset()[0], b.reset()[0], c.reset()[0]
    assert np.array_equal(oa["rgb"], ob["rgb"]) and not np.array_equal(oa["rgb"], oc["rgb"])
    assert oa["rgb"].shape == (64, 64, 3) and oa["rgb"].dtype == np.uint8
    for _ in range(5):
        ra, rb = a.step(1), b.step(1)
        assert np.array_equal(ra[0]["rgb"], rb[0]["rgb"]) and ra[1:4] == rb[1:4]


def test_every_emission_names_itself_and_rows_all_differ():
    env = SyntheticEnv("navigate4", bench_seed=7, rank=3)
    obs, _ = env.reset()
    frames = [obs["rgb"]]
    for _ in range(600):  # six scenes of 100 emissions; an episode end does not restart the count
        frames.append(env.step(0)[0]["rgb"])
    assert [SyntheticEnv.decode(f) for f in frames] == [(3, n) for n in range(601)]
    assert len({f.tobytes() for f in frames}) == len(frames)
    means = np.array([f.mean() for f in frames])
    assert means.std() > 10  # scenes differ in brightness: batch columns are not alike
    assert all(np.array_equal(env.frame("rgb", n), frames[n]) for n in (0, 1, 64, 130, 600))


def test_episode_ends_are_logged_and_stamps_cover_every_step():
    mix = load_mix("navigate4")
    env = SyntheticEnv("navigate4", bench_seed=11, rank=0)
    env.reset()
    first = mix["first_episode_steps"][0]
    done_at = None
    for t in range(first + 3):
        _, _, term, trunc, _ = env.step(2)
        if term or trunc:
            done_at = t + 1
            env.reset()
    assert done_at == first
    n_final = env.log_final.index(True)
    assert n_final == first and (env.log_terminated[n_final] or env.log_truncated[n_final])
    assert env.log_first[n_final + 1] and env.log_action[n_final] is None and env.log_action[n_final - 1] is not None and env.log_reward[n_final + 1] == 0.0
    assert len(env.t_enter) == len(env.t_exit) == first + 3
    assert all(b >= a for a, b in zip(env.t_enter, env.t_exit)) and REGISTRY[0] is env


@pytest.mark.parametrize("cell,bench_file", ANY_CELLS)
def test_mix_files_state_what_the_cells_why_says(cell, bench_file):
    pb_checks.why_names_the_mixs_envs(cell, bench_file)


@pytest.mark.parametrize("path", mix_files(), ids=os.path.basename)
def test_every_mix_names_a_generator_that_keeps_what_run_py_needs_of_it(path):
    """The contract in `envs.py`'s docstring: constructor arguments, registration by env index, stamps per step(), seeded."""
    with open(path) as f:
        mix = json.load(f)
    cls = generator_of(mix)
    assert f"{cls.__module__}.{cls.__qualname__}" == mix["generator"]
    assert list(inspect.signature(cls.__init__).parameters)[1:5] == ["mix", "seed", "rank", "bench_seed"]
    reset_registry()
    ref = os.path.relpath(path, ROOT) if "fixtures" in path else mix["name"]  # as run.py tells a generator its mix
    a, b = cls(mix=ref, seed=0, rank=1, bench_seed=3000000019), cls(mix=ref, seed=5, rank=1, bench_seed=3000000019)
    assert REGISTRY[1] is b and a.observation_space == b.observation_space
    (oa, _), (ob, _) = a.reset(), b.reset()
    for _ in range(3):
        action = a.action_space.sample()
        ra, rb = a.step(action), b.step(action)
        assert all(np.array_equal(ra[0][k], rb[0][k]) for k in ra[0]) and ra[1:4] == rb[1:4]
    assert len(a.t_enter) == len(a.t_exit) == len(a.self_s) == 3 and all(x <= y for x, y in zip(a.t_enter, a.t_exit))
    assert all(np.array_equal(oa[k], ob[k]) for k in oa)


def test_a_vector_emission_names_itself_and_is_rebuilt_from_the_log():
    env = VectorEnv("tests/perfbench/fixtures/traffic/vec8.json", bench_seed=2**31 + 5, rank=1)
    obs = [env.reset()[0]["state"]] + [env.step(0)[0]["state"] for _ in range(30)]
    assert [VectorEnv.decode(o) for o in obs] == [(1, n) for n in range(31)]
    assert all(o.dtype == np.float32 and o.shape == (8,) for o in obs)
    assert all(np.array_equal(env.vector("state", n), obs[n]) for n in (0, 7, 30)) and len({o.tobytes() for o in obs}) == 31
    assert env.log_final.index(True) == 23  # the mix's first episode of env 1
