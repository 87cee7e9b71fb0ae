"""The benchmark's traffic: seeded synthetic gymnasium envs, driven by a mix file.

A mix (``<home>/traffic/<mix>.json``) names its generator under
``"generator"``, a dotted class path, and the program reaches it through
``env.wrapper._target_=<that path>``. Everything that makes a mix (observation
keys, shapes and dtypes, the action space, episode lengths, scene length,
rewards) is data in the mix's file; a generator is general code.

What `run.py` and the adapters need of ANY generator (checked by
tests/perfbench/test_pb_envs.py for every class a mix names):

* the constructor takes ``mix, seed, rank, bench_seed``: the mix's name (or
  its file's path from the root of the checkout), the program's seed (not
  used), the env's index, and the benchmark's ``--seed``;
* it registers itself in ``envs.REGISTRY`` under its env index, in this
  process (`env.sync_env=True`);
* every ``step()`` call is stamped with the host clock on entry and on return
  in the lists ``t_enter`` / ``t_exit`` (and ``self_s``, their difference).
  Those stamps are taken outside the program and are the source of the
  end-to-end metrics;
* the same ``bench_seed`` and ``rank`` give the same emissions.

The two generators here also keep their own log of every observation they
emitted (which frame, reward, flags, and the action that answered it), and
every emission names itself (`decode`), so that the rows a replay ring gathers
or a rollout holds can be checked one by one against what the generator really
produced.
"""
from __future__ import annotations

import importlib
import json
import os
import time
from typing import Any, Dict, List, Optional

import gymnasium as gym
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# env index -> the live env (SyncVectorEnv keeps the envs in this process)
REGISTRY: Dict[int, "SyntheticEnv"] = {}


def load_mix(name: str) -> Dict[str, Any]:
    """A mix by its name (`perfbench/traffic/<name>.json`) or, for a mix that
    lies elsewhere, by its file's path from the root of the checkout."""
    path = os.path.join(os.path.dirname(HERE), name) if name.endswith(".json") else os.path.join(HERE, "traffic", f"{name}.json")
    with open(path) as f:
        return json.load(f)


def generator_of(mix: Dict[str, Any]) -> type:
    """The class a mix names under `generator`."""
    module, _, cls = str(mix["generator"]).rpartition(".")
    return getattr(importlib.import_module(module), cls)


def reset_registry() -> None:
    REGISTRY.clear()


def _rng(seed: int, *stream: int) -> np.random.Generator:
    # seeds reach a little over 2**31: SeedSequence takes any non-negative int
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, *stream])


class SyntheticEnv(gym.Env):
    """Emission ``n`` of env ``e`` (every observation it hands out, resets
    and final observations included) is fully determined by (seed, e, n):

    * image keys: ``clip(level[scene] + contrast[scene] * noise[n % pool])``
      with the first six bytes overwritten by (e, n) so a gathered row names
      the emission it claims to be; scenes of ``scene_steps`` emissions differ
      in brightness and contrast, so rows and batch columns all differ;
    * vector keys other than ``reward``: seeded normals per emission;
    * the reward: drawn per emission from the mix's ``reward_values`` in the
      scenes that pay (``reward_scene_share`` of them), nought in the others.
    """

    metadata = {"render_modes": ["rgb_array"]}

    def __init__(self, mix: str, seed: int = 0, rank: int = 0, bench_seed: Optional[int] = None):
        self.mix = load_mix(mix) if isinstance(mix, str) else dict(mix)
        self.index = int(rank)
        self.seed_value = int(bench_seed if bench_seed is not None else seed)
        m = self.mix
        spaces: Dict[str, gym.Space] = {}
        self.image_keys: List[str] = []
        self.vector_keys: List[str] = []
        for key, spec in m["observation"].items():
            dtype = np.dtype(spec["dtype"])
            shape = tuple(spec["shape"])
            if dtype == np.uint8:
                spaces[key] = gym.spaces.Box(0, 255, shape, np.uint8)
                self.image_keys.append(key)
            else:
                spaces[key] = gym.spaces.Box(-np.inf, np.inf, shape, dtype)
                self.vector_keys.append(key)
        self.observation_space = gym.spaces.Dict(spaces)
        act = m["action"]
        if act["type"] == "discrete":
            self.action_space = gym.spaces.Discrete(int(act["n"]))
        elif act["type"] == "box":
            self.action_space = gym.spaces.Box(-1.0, 1.0, (int(act["n"]),), np.float32)
        else:
            raise ValueError(f"unknown action type {act['type']!r}")
        self.render_mode = "rgb_array"

        self.episode_steps = int(m["episode_steps"])
        firsts = m.get("first_episode_steps") or [self.episode_steps]
        self.first_episode_steps = int(firsts[self.index % len(firsts)])
        self.scene_steps = int(m["scene_steps"])
        self.pool = int(m["pool_frames"])
        self.terminate_share = float(m.get("terminate_share", 0.5))
        self.reward_values = np.asarray(m["reward_values"], np.float32)

        r = _rng(self.seed_value, self.index, 1)
        self._noise = {
            k: r.integers(-128, 128, (self.pool,) + tuple(spaces[k].shape), dtype=np.int16)
            for k in self.image_keys
        }
        n_scenes = 4096
        self._level = r.integers(0, 256, n_scenes).astype(np.int16)
        self._contrast = r.integers(0, 5, n_scenes).astype(np.int16)  # noise >> (4 - c)
        self._reward_idx = r.integers(0, len(self.reward_values), 1 << 16)
        self._rich = r.random(n_scenes) < float(m.get("reward_scene_share", 0.5))  # scenes that pay at all
        self._terminates = r.random(1 << 12) < self.terminate_share
        self._vec_seed = int(r.integers(0, 2**31 - 1))

        # the env's own log, one entry per emission
        self.n = 0  # emissions so far
        self.log_reward: List[float] = []
        self.log_terminated: List[bool] = []
        self.log_truncated: List[bool] = []
        self.log_first: List[bool] = []
        self.log_final: List[bool] = []  # a final observation (closing row)
        self.log_action: List[Any] = []  # the action that answered emission n (None: none)
        # stamps of step(): entry and return, host clock
        self.t_enter: List[float] = []
        self.t_exit: List[float] = []
        self.self_s: List[float] = []
        self._episode = 0
        self._t_in_episode = 0
        self._last_emission: Optional[int] = None
        REGISTRY[self.index] = self

    # -- content of an emission, reproducible without the env's state -------
    def frame(self, key: str, n: int) -> np.ndarray:
        scene = (n // self.scene_steps) % len(self._level)
        noise = self._noise[key][n % self.pool]
        img = np.clip(self._level[scene] + (noise >> (4 - self._contrast[scene])), 0, 255).astype(np.uint8)
        flat = img.reshape(-1)
        flat[0] = self.index & 0xFF
        flat[1:5] = np.frombuffer(np.uint32(n).tobytes(), np.uint8)
        flat[5] = 0xA5
        return img

    def vector(self, key: str, n: int) -> np.ndarray:
        shape = self.observation_space[key].shape
        return _rng(self._vec_seed, n).standard_normal(shape).astype(np.float32)

    def reward_of(self, n: int) -> float:
        if not self._rich[(n // self.scene_steps) % len(self._rich)]:
            return 0.0
        return float(self.reward_values[self._reward_idx[n % len(self._reward_idx)]])

    @staticmethod
    def decode(img: np.ndarray) -> tuple:
        """(env index, emission) stamped into an image, or (-1, -1)."""
        flat = np.ascontiguousarray(img).reshape(-1)
        if flat[5] != 0xA5:
            return -1, -1
        return int(flat[0]), int(np.frombuffer(flat[1:5].tobytes(), np.uint32)[0])

    def _emit(self, reward: float, terminated: bool, truncated: bool, first: bool, final: bool) -> Dict[str, Any]:
        n = self.n
        obs: Dict[str, Any] = {k: self.frame(k, n) for k in self.image_keys}
        for k in self.vector_keys:
            obs[k] = np.array([reward], np.float32) if k == "reward" else self.vector(k, n)
        self.log_reward.append(reward)
        self.log_terminated.append(terminated)
        self.log_truncated.append(truncated)
        self.log_first.append(first)
        self.log_final.append(final)
        self.log_action.append(None)
        self._last_emission = n
        self.n = n + 1
        return obs

    # -- gymnasium ---------------------------------------------------------
    def reset(self, seed: Optional[int] = None, options: Optional[dict] = None):
        super().reset(seed=seed)
        self._t_in_episode = 0
        return self._emit(0.0, False, False, True, False), {}

    def step(self, action: Any):
        t0 = time.perf_counter()
        self.t_enter.append(t0)
        if self._last_emission is not None:
            self.log_action[self._last_emission] = np.asarray(action).copy()
        self._t_in_episode += 1
        limit = self.first_episode_steps if self._episode == 0 else self.episode_steps
        done = self._t_in_episode >= limit
        terminated = bool(done and self._terminates[self._episode % len(self._terminates)])
        truncated = bool(done and not terminated)
        obs = self._emit(self.reward_of(self.n), terminated, truncated, False, done)
        if done:
            self._episode += 1
        t1 = time.perf_counter()
        self.t_exit.append(t1)
        self.self_s.append(t1 - t0)
        return obs, self.log_reward[-1], terminated, truncated, {}

    def render(self):
        k = self.image_keys[0]
        return self.frame(k, max(self.n - 1, 0))

    def close(self):
        pass


class VectorEnv(SyntheticEnv):
    """The same generator for a mix without an image: every vector key's
    first two entries are the env's index and the emission's number (float32
    holds whole numbers up to 2**24 exactly), the rest seeded normals, so that
    a row of a rollout names the emission it claims to be."""

    def vector(self, key: str, n: int) -> np.ndarray:
        vec = super().vector(key, n)
        flat = vec.reshape(-1)
        flat[0], flat[1] = float(self.index), float(n)
        return vec

    @staticmethod
    def decode(vec: np.ndarray) -> tuple:
        """(env index, emission) stamped into a vector."""
        flat = np.asarray(vec).reshape(-1)
        return int(flat[0]), int(flat[1])

    def render(self):
        return np.zeros((8, 8, 3), np.uint8)
