"""Deterministic dummy envs — the test fake backend.

Counterpart of reference sheeprl/envs/dummy.py:8-108: dict observations
{rgb, state} with deterministic step-counter content, fixed-length episodes.
Images are NHWC (TPU layout) unlike the reference's CHW.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import gymnasium as gym
import numpy as np


class BaseDummyEnv(gym.Env):
    def __init__(
        self,
        image_size: Tuple[int, int, int] = (64, 64, 3),
        n_steps: int = 128,
        vector_shape: Tuple[int, ...] = (10,),
        dict_obs_space: bool = True,
    ):
        self._dict_obs_space = dict_obs_space
        if dict_obs_space:
            self.observation_space = gym.spaces.Dict(
                {
                    "rgb": gym.spaces.Box(0, 255, shape=image_size, dtype=np.uint8),
                    "state": gym.spaces.Box(-20, 20, shape=vector_shape, dtype=np.float32),
                }
            )
        else:
            self.observation_space = gym.spaces.Box(-20, 20, shape=vector_shape, dtype=np.float32)
        self.reward_range = (-np.inf, np.inf)
        self._current_step = 0
        self._n_steps = n_steps
        self.render_mode = "rgb_array"

    def get_obs(self) -> Any:
        if self._dict_obs_space:
            return {
                "rgb": np.full(
                    self.observation_space["rgb"].shape, self._current_step % 256, dtype=np.uint8
                ),
                "state": np.full(
                    self.observation_space["state"].shape, self._current_step, dtype=np.float32
                ),
            }
        return np.full(self.observation_space.shape, self._current_step, dtype=np.float32)

    def step(self, action: Any):
        done = self._current_step == self._n_steps
        self._current_step += 1
        return self.get_obs(), 0.0, done, False, {}

    def reset(self, seed: Optional[int] = None, options: Optional[dict] = None):
        super().reset(seed=seed)
        self._current_step = 0
        return self.get_obs(), {}

    def render(self):
        if self._dict_obs_space:
            return self.get_obs()["rgb"]
        return np.zeros((64, 64, 3), dtype=np.uint8)

    def close(self):
        pass


class ContinuousDummyEnv(BaseDummyEnv):
    def __init__(self, action_dim: int = 2, **kwargs: Any):
        self.action_space = gym.spaces.Box(-1.0, 1.0, shape=(action_dim,), dtype=np.float32)
        super().__init__(**kwargs)


class DiscreteDummyEnv(BaseDummyEnv):
    def __init__(self, action_dim: int = 2, n_steps: int = 4, **kwargs: Any):
        self.action_space = gym.spaces.Discrete(action_dim)
        super().__init__(n_steps=n_steps, **kwargs)


class MultiDiscreteDummyEnv(BaseDummyEnv):
    def __init__(self, action_dims: Optional[List[int]] = None, **kwargs: Any):
        self.action_space = gym.spaces.MultiDiscrete(action_dims or [2, 2])
        super().__init__(**kwargs)


class CrashingDummyEnv(DiscreteDummyEnv):
    """Discrete dummy that raises mid-episode every `crash_every` cumulative
    steps — drives the fault-tolerance path (RestartOnException + buffer
    restart surgery, reference dreamer_v3.py:385-399, :595-608)."""

    def __init__(self, crash_every: int = 3, **kwargs: Any):
        super().__init__(**kwargs)
        self._crash_every = int(crash_every)
        self._lifetime_steps = 0

    def step(self, action: Any):
        self._lifetime_steps += 1
        if self._lifetime_steps % self._crash_every == 0:
            raise RuntimeError(f"scripted crash at lifetime step {self._lifetime_steps}")
        return super().step(action)


class TokenDummyEnv(gym.Env):
    """One token id a step: the observation is the agent's last action echoed (id 0 after a reset), the action any id
    of `Discrete(vocab)`, episodes of `n_steps` steps, reward 1 at the last step if the episode's ids were not all
    alike. What a sequence policy (`exp=ppo_recurrent_xing4`) acts on where no text environment is installed."""

    def __init__(self, vocab: int = 16, n_steps: int = 8):
        self.observation_space = gym.spaces.Dict({"token": gym.spaces.Box(0, vocab - 1, (1,), np.int32)})
        self.action_space = gym.spaces.Discrete(int(vocab))
        self._n_steps = int(n_steps)
        self._seen: List[int] = []
        self.render_mode = "rgb_array"

    def reset(self, seed: Optional[int] = None, options: Optional[dict] = None):
        super().reset(seed=seed)
        self._seen = []
        return {"token": np.zeros((1,), np.int32)}, {}

    def step(self, action: Any):
        self._seen.append(int(action))
        done = len(self._seen) >= self._n_steps
        reward = float(done and len(set(self._seen)) > 1)
        return {"token": np.array([int(action)], np.int32)}, reward, done, False, {}

    def render(self):
        return np.zeros((64, 64, 3), dtype=np.uint8)

    def close(self):
        pass


class TokenBlockDummyEnv(gym.Env):
    """A block of `block` token ids a step: the observation is the agent's last action block echoed (ids 0 after a
    reset), the action a block of ids of `MultiDiscrete([vocab] x block)`, episodes of `n_steps` steps, reward 1 at
    the last step if the episode's ids were not all alike. What a block-diffusion policy (`exp=ppo_recurrent_sdar`)
    acts on where no text environment is installed."""

    def __init__(self, vocab: int = 16, block: int = 4, n_steps: int = 8):
        self.observation_space = gym.spaces.Dict({"block": gym.spaces.Box(0, vocab - 1, (int(block),), np.int32)})
        self.action_space = gym.spaces.MultiDiscrete([int(vocab)] * int(block))
        self._block, self._n_steps = int(block), int(n_steps)
        self._seen: List[int] = []
        self._steps = 0
        self.render_mode = "rgb_array"

    def reset(self, seed: Optional[int] = None, options: Optional[dict] = None):
        super().reset(seed=seed)
        self._seen, self._steps = [], 0
        return {"block": np.zeros((self._block,), np.int32)}, {}

    def step(self, action: Any):
        ids = np.asarray(action, np.int32).reshape(self._block)
        self._seen += ids.tolist()
        self._steps += 1
        done = self._steps >= self._n_steps
        reward = float(done and len(set(self._seen)) > 1)
        return {"block": ids}, reward, done, False, {}

    def render(self):
        return np.zeros((64, 64, 3), dtype=np.uint8)

    def close(self):
        pass
