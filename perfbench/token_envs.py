"""Traffic of a sequence policy: seeded synthetic envs that emit one token id a
step, driven by a mix file like the generators of `envs.py` and kept to the
same contract (constructor `mix, seed, rank, bench_seed`; registered in
`envs.REGISTRY`; every `step()` stamped on entry and return; the same seed
gives the same emissions for the same actions).

An env stands for one generation session behind a language policy: the
observation is the id the env appends to the context, which is the agent's
last action echoed or, with the mix's `own_token_share`, an id of the env's
own (as a tool's answer is); the action is any id of the held vocabulary
slice. Every `rollout_steps` steps are tiled by whole episodes whose lengths
are multiples of `episode_unit`, drawn per env and per rollout from the seed,
so no episode crosses a rollout boundary; an episode's last step pays a
seeded function of the episode's tokens, every other step nothing. Episodes
end by termination, so no reward carries a bootstrap.

The env logs every emission (the token, its reward and flags, the action that
answered it). A token cannot carry a stamp of its own, so a rollout is matched
to the log by order: the observations an agent sees are the emissions that are
not final, in order (`seen`).
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import gymnasium as gym
import numpy as np

from perfbench.envs import REGISTRY, _rng, load_mix


class TokenEpisodesEnv(gym.Env):
    metadata = {"render_modes": ["rgb_array"]}

    def __init__(self, mix: str, seed: int = 0, rank: int = 0, bench_seed: Optional[int] = None):
        self.mix = load_mix(mix) if isinstance(mix, str) else dict(mix)
        self.index = int(rank)
        self.seed_value = int(bench_seed if bench_seed is not None else seed)
        m = self.mix
        (self.key, spec), = m["observation"].items()
        if np.dtype(spec["dtype"]) != np.int32 or list(spec["shape"]) != [1] or m["action"]["type"] != "discrete":
            raise ValueError("a token mix has one int32[1] observation and a discrete action")
        self.vocab = int(m["action"]["n"])
        self.observation_space = gym.spaces.Dict({self.key: gym.spaces.Box(0, self.vocab - 1, (1,), np.int32)})
        self.action_space = gym.spaces.Discrete(self.vocab)
        self.render_mode = "rgb_array"
        self.rollout_steps, self.unit = int(m["rollout_steps"]), int(m["episode_unit"])
        if self.rollout_steps % self.unit:
            raise ValueError("rollout_steps is a whole number of episode units")
        self.cut_share = float(m["cut_share"])

        r = _rng(self.seed_value, self.index, 1)
        pool = 1 << 16
        self._own = r.integers(0, self.vocab, pool).astype(np.int32)  # the env's own ids, by emission
        self._is_own = r.random(pool) < float(m["own_token_share"])
        self._worth = r.standard_normal(self.vocab).astype(np.float32)  # what an id adds to an episode's pay

        self.n = 0  # emissions so far
        self.log_token: List[int] = []
        self.log_reward: List[float] = []
        self.log_first: List[bool] = []
        self.log_final: List[bool] = []
        self.log_action: List[Any] = []  # the action that answered emission n (None: none)
        self.t_enter: List[float] = []
        self.t_exit: List[float] = []
        self.self_s: List[float] = []
        self._steps = 0  # steps taken so far: their place in the rollout decides where episodes end
        self._ends: List[int] = []  # the steps (counted from the env's start) at which the episodes of one rollout end
        self._episode: List[int] = []  # the tokens of the running episode
        self._last_emission: Optional[int] = None
        REGISTRY[self.index] = self

    def episode_ends(self, rollout: int) -> List[int]:
        """Where the episodes of that rollout end, as steps within it (the last one is `rollout_steps`)."""
        units = self.rollout_steps // self.unit
        cuts = _rng(self.seed_value, self.index, 2, rollout).random(units - 1) < self.cut_share
        return [self.unit * (i + 1) for i in range(units - 1) if cuts[i]] + [self.rollout_steps]

    def pay(self, tokens: List[int]) -> float:
        return float(np.float32(np.mean(self._worth[np.asarray(tokens)])))

    def _emit(self, token: int, reward: float, first: bool, final: bool) -> Dict[str, Any]:
        self.log_token.append(int(token))
        self.log_reward.append(reward)
        self.log_first.append(first)
        self.log_final.append(final)
        self.log_action.append(None)
        self._last_emission = self.n
        self.n += 1
        return {self.key: np.array([token], np.int32)}

    def reset(self, seed: Optional[int] = None, options: Optional[dict] = None):
        super().reset(seed=seed)
        token = int(self._own[self.n % len(self._own)])  # an episode opens with an id of the env's own
        self._episode = [token]
        return self._emit(token, 0.0, True, False), {}

    def step(self, action: Any):
        t0 = time.perf_counter()
        self.t_enter.append(t0)
        action = int(np.asarray(action).reshape(-1)[0])
        self.log_action[self._last_emission] = action
        if not self._ends:
            base = self._steps - self._steps % self.rollout_steps
            self._ends = [base + e for e in self.episode_ends(self._steps // self.rollout_steps)]
        self._steps += 1
        done = self._steps == self._ends[0]
        i = self.n % len(self._own)
        token = int(self._own[i]) if self._is_own[i] else action
        self._episode.append(token)
        reward = self.pay(self._episode) if done else 0.0
        if done:
            self._ends.pop(0)
        obs = self._emit(token, reward, False, done)
        t1 = time.perf_counter()
        self.t_exit.append(t1)
        self.self_s.append(t1 - t0)
        return obs, reward, done, False, {}

    def seen(self) -> List[int]:
        """The emissions an agent acts on, in order: a final observation is followed at once by the next episode's first."""
        return [n for n in range(self.n) if not self.log_final[n]]

    def render(self):
        return np.zeros((8, 8, 3), np.uint8)

    def close(self):
        pass
