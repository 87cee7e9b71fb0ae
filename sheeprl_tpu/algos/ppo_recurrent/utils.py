"""Recurrent-PPO per-algo contract (reference ppo_recurrent/utils.py)."""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

AGGREGATOR_KEYS = {
    "Rewards/rew_avg",
    "Game/ep_len_avg",
    "Loss/value_loss",
    "Loss/policy_loss",
    "Loss/entropy_loss",
}
MODELS_TO_REGISTER = {"agent"}


def update_coefs(cfg: Any, update_iter: int, num_updates: int) -> Dict[str, jax.Array]:
    """The coefficients one update is given (both backbones' loops): clip and entropy annealed where the recipe says
    so, the value coefficient, and the learning rate's fraction."""
    from ...utils.utils import linear_annealing

    a = cfg.algo
    return {
        "clip_coef": jnp.asarray(linear_annealing(a.clip_coef, update_iter - 1, num_updates) if a.anneal_clip_coef else a.clip_coef, jnp.float32),
        "ent_coef": jnp.asarray(linear_annealing(a.ent_coef, update_iter - 1, num_updates) if a.anneal_ent_coef else a.ent_coef, jnp.float32),
        "vf_coef": jnp.asarray(a.vf_coef, jnp.float32),
        "lr_frac": jnp.asarray(1.0 - (update_iter - 1) / max(num_updates, 1) if a.anneal_lr else 1.0, jnp.float32),
    }


def prepare_obs(
    obs: Dict[str, np.ndarray], cnn_keys=(), mlp_keys=(), num_envs: int = 1
) -> Dict[str, jax.Array]:
    """Host obs shaped with a leading sequence axis of 1 ([1, N, ...],
    reference ppo_recurrent/utils.py prepare_obs). Stays NUMPY — the jitted
    consumer transfers it to wherever its committed params live."""
    out: Dict[str, np.ndarray] = {}
    for k in cnn_keys:
        out[k] = np.asarray(obs[k]).reshape(1, num_envs, *np.asarray(obs[k]).shape[-3:])
    for k in mlp_keys:
        out[k] = np.asarray(obs[k], dtype=np.float32).reshape(1, num_envs, -1)
    return out


def test(module: Any, params: Any, env: Any, cfg: Any, log_dir: str, logger=None) -> float:
    """Greedy episode carrying the LSTM state (reference utils.py test)."""
    from .agent import actions_and_log_probs

    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    act_width = int(sum(module.actions_dim))

    @jax.jit
    def act(p, o, prev_a, carry):
        actor_out, _, carry = module.apply(
            {"params": p}, o, prev_a, jnp.zeros((1, 1, 1)), carry
        )
        actor_out = [a[0] for a in actor_out]
        actions, _, _ = actions_and_log_probs(actor_out, module.is_continuous, greedy=True)
        return actions, carry

    from ...parallel.placement import place_for_inference, player_device

    pdev = player_device(cfg)
    params = place_for_inference(cfg, params)

    done = False
    cumulative_rew = 0.0
    obs, _ = env.reset(seed=cfg.seed)
    carry = jax.device_put(module.initial_states(1), pdev)
    prev_actions = np.zeros((1, 1, act_width), np.float32)
    while not done:
        device_obs = prepare_obs(obs, cnn_keys, mlp_keys, 1)
        actions, carry = act(params, device_obs, prev_actions, carry)
        np_actions = np.asarray(actions)
        if module.is_continuous:
            env_actions = np_actions.reshape(env.action_space.shape)
            prev_actions = np_actions.astype(np.float32).reshape(1, 1, -1)
        else:
            oh = []
            for i, d in enumerate(module.actions_dim):
                oh.append(np.eye(d, dtype=np.float32)[np_actions.reshape(1, -1)[:, i]])
            prev_actions = np.concatenate(oh, -1).astype(np.float32).reshape(1, 1, -1)
            if np_actions.shape[-1] > 1:
                env_actions = np_actions.reshape(-1)
            else:
                env_actions = np_actions.reshape(()).item()
        obs, reward, terminated, truncated, _ = env.step(env_actions)
        done = bool(terminated or truncated)
        cumulative_rew += float(reward)
        if cfg.get("dry_run", False):
            done = True
    if logger is not None:
        logger.log_metrics({"Test/cumulative_reward": cumulative_rew}, 0)
    print(f"Test - Reward: {cumulative_rew}")
    env.close()
    return cumulative_rew
