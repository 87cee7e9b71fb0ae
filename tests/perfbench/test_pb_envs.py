"""The traffic generator: seeded, stamped, and its own log is enough to rebuild any row."""
import numpy as np
import pytest

from perfbench.envs import REGISTRY, SyntheticEnv, load_mix


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


def test_mix_files_state_what_the_cells_why_says(benchmark_json):
    for cell in benchmark_json["workloads"]:
        mix = load_mix(cell["traffic"])
        assert f"{mix['num_envs']} env" in cell["why"]
        assert mix["episode_steps"] == 500 and mix["warmup_train_calls"] >= 4
