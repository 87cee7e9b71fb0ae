"""Traffic of a block-diffusion policy: seeded synthetic envs that emit a block
of token ids a step, driven by a mix file and kept to the contract of
`envs.py` (constructor `mix, seed, rank, bench_seed`; registered in
`envs.REGISTRY`; every `step()` stamped on entry and return; the same seed
gives the same emissions for the same actions).

An env stands for one generation session behind a language policy that
commits a block of ids at a time: the observation is the block the env
appends to the context, which is the agent's last action block echoed with
each id replaced, with the mix's `own_token_share`, by an id of the env's own
(never the mix's `mask_id`); the action is a block of ids of the held
vocabulary slice. Episodes tile every rollout as `token_envs.TokenEpisodesEnv`
has them (whole multiples of `episode_unit` blocks, drawn per env and per
rollout from the seed); an episode's last step pays a seeded function of the
episode's ids, every other step nothing; episodes end by termination.

The env logs every emission (the block, its reward and flags, the action block
that answered it), matched to a rollout by order as the one-token env is.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import gymnasium as gym
import numpy as np

from perfbench.envs import REGISTRY, _rng, load_mix
from perfbench.token_envs import TokenEpisodesEnv


class BlockEpisodesEnv(TokenEpisodesEnv):

    def __init__(self, mix: str, seed: int = 0, rank: int = 0, bench_seed: Optional[int] = None):
        self.mix = load_mix(mix) if isinstance(mix, str) else dict(mix)
        self.index = int(rank)
        self.seed_value = int(bench_seed if bench_seed is not None else seed)
        m = self.mix
        (self.key, spec), = m["observation"].items()
        act = m["action"]
        self.block = int(spec["shape"][0])
        if np.dtype(spec["dtype"]) != np.int32 or act["type"] != "multi_discrete" or int(act["size"]) != self.block:
            raise ValueError("a block mix has one int32[L] observation and a multi_discrete action of L ids")
        self.vocab, self.mask_id = int(act["n"]), int(m["mask_id"])
        self.observation_space = gym.spaces.Dict({self.key: gym.spaces.Box(0, self.vocab - 1, (self.block,), np.int32)})
        self.action_space = gym.spaces.MultiDiscrete([self.vocab] * self.block)
        self.render_mode = "rgb_array"
        self.rollout_steps, self.unit = int(m["rollout_steps"]), int(m["episode_unit"])
        if self.rollout_steps % self.unit:
            raise ValueError("rollout_steps is a whole number of episode units")
        self.cut_share = float(m["cut_share"])

        r = _rng(self.seed_value, self.index, 1)
        pool = 1 << 14
        own = r.integers(0, self.vocab - 1, pool * self.block).astype(np.int32)  # the env's own ids, never the mask
        self._own = np.where(own >= self.mask_id, own + 1, own).reshape(pool, self.block)
        self._is_own = r.random((pool, self.block)) < float(m["own_token_share"])
        self._worth = r.standard_normal(self.vocab).astype(np.float32)  # what an id adds to an episode's pay

        self.n = 0  # emissions so far
        self.log_block: List[np.ndarray] = []
        self.log_reward: List[float] = []
        self.log_first: List[bool] = []
        self.log_final: List[bool] = []
        self.log_action: List[Any] = []  # the action block that answered emission n (None: none)
        self.t_enter: List[float] = []
        self.t_exit: List[float] = []
        self.self_s: List[float] = []
        self._steps = 0
        self._ends: List[int] = []
        self._episode: List[int] = []
        self._last_emission: Optional[int] = None
        REGISTRY[self.index] = self

    def _emit_block(self, block: np.ndarray, reward: float, first: bool, final: bool) -> Dict[str, Any]:
        self.log_block.append(block.copy())
        self.log_reward.append(reward)
        self.log_first.append(first)
        self.log_final.append(final)
        self.log_action.append(None)
        self._last_emission = self.n
        self.n += 1
        return {self.key: block.copy()}

    def reset(self, seed: Optional[int] = None, options: Optional[dict] = None):
        gym.Env.reset(self, seed=seed)
        block = self._own[self.n % len(self._own)]  # an episode opens with ids of the env's own
        self._episode = block.tolist()
        return self._emit_block(block, 0.0, True, False), {}

    def step(self, action: Any):
        t0 = time.perf_counter()
        self.t_enter.append(t0)
        action = np.asarray(action, np.int32).reshape(self.block)
        self.log_action[self._last_emission] = action.copy()
        if not self._ends:
            base = self._steps - self._steps % self.rollout_steps
            self._ends = [base + e for e in self.episode_ends(self._steps // self.rollout_steps)]
        self._steps += 1
        done = self._steps == self._ends[0]
        i = self.n % len(self._own)
        block = np.where(self._is_own[i], self._own[i], action).astype(np.int32)
        self._episode += block.tolist()
        reward = self.pay(self._episode) if done else 0.0
        if done:
            self._ends.pop(0)
        obs = self._emit_block(block, reward, False, done)
        t1 = time.perf_counter()
        self.t_exit.append(t1)
        self.self_s.append(t1 - t0)
        return obs, reward, done, False, {}
