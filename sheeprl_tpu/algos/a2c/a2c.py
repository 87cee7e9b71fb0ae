"""A2C — coupled on-policy training (Template A).

Reference sheeprl/algos/a2c/a2c.py (383 LoC). Same rollout/GAE skeleton as
PPO; the update accumulates gradients over minibatches and steps once
(reference a2c.py:52-102). With sum-reduction that is mathematically one
gradient over the whole batch, so the TPU version is a single jitted,
donated-argument step on the full rollout — no minibatch loop at all.
"""
from __future__ import annotations

import os
from functools import partial
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from ...config import Config, instantiate
from ...data import ReplayBuffer
from ...ops import gae as gae_op
from ...optim import clipped
from ...parallel import Distributed
from ...parallel.placement import make_param_mirror
from ...telemetry import Telemetry
from ...utils.checkpoint import CheckpointManager
from ...utils.env import episode_stats, vectorize
from ...utils.logger import get_log_dir, get_logger
from ...utils.registry import register_algorithm, register_evaluation
from ...resilience import RunGuard
from ...utils import run_info
from ...utils.utils import save_configs
from ..ppo.utils import prepare_obs, test
from .agent import actions_and_log_probs, build_agent
from .loss import policy_loss, value_loss

AGGREGATOR_KEYS = {"Rewards/rew_avg", "Game/ep_len_avg", "Loss/value_loss", "Loss/policy_loss"}
MODELS_TO_REGISTER = {"agent"}


def make_update_fn(module, tx, cfg: Config):
    reduction = str(cfg.algo.loss_reduction)

    def loss_fn(params, data: Dict[str, jax.Array]):
        obs = {k[4:]: v for k, v in data.items() if k.startswith("obs:")}
        actor_out, new_values = module.apply({"params": params}, obs)
        actions = data["actions"]
        if not module.is_continuous:
            actions = actions.astype(jnp.int32)
        _, logprobs, _ = actions_and_log_probs(actor_out, module.is_continuous, actions=actions)
        pg = policy_loss(logprobs, data["advantages"], reduction)
        vl = value_loss(new_values, data["returns"], reduction)
        return pg + vl, {"Loss/policy_loss": pg, "Loss/value_loss": vl}

    @partial(jax.jit, donate_argnums=(0, 1))
    def update(params, opt_state, data):
        (_, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, data)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, aux

    return update


@register_algorithm(name="a2c")
def main(dist: Distributed, cfg: Config) -> None:
    root_key = dist.seed_everything(cfg.seed)
    rank = dist.process_index
    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, rank)
    if rank == 0:
        save_configs(cfg, log_dir)

    envs = vectorize(cfg, cfg.seed, rank, log_dir)
    obs_space = envs.single_observation_space
    action_space = envs.single_action_space
    num_envs = int(cfg.env.num_envs)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    obs_keys = mlp_keys
    if not isinstance(obs_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {obs_space}")

    state = None
    if cfg.checkpoint.resume_from:
        state = CheckpointManager.load(cfg.checkpoint.resume_from)
    root_key, init_key = jax.random.split(state["rng"] if state else root_key)
    module, params = build_agent(
        dist, cfg, obs_space, action_space, init_key, state["params"] if state else None
    )
    tx = clipped(instantiate(cfg.algo.optimizer), cfg.algo.get("max_grad_norm", 0.0))
    opt_state = dist.replicate(state["opt_state"] if state else tx.init(params))

    rollout_steps = int(cfg.algo.rollout_steps)
    rb = ReplayBuffer(
        rollout_steps,
        num_envs,
        obs_keys=obs_keys,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}") if cfg.buffer.memmap else None,
        seed=cfg.seed + 1024 * rank,
    )

    from ..ppo.ppo import make_act_fn, make_value_fn

    act = make_act_fn(module)
    value_fn = make_value_fn(module)
    update = make_update_fn(module, tx, cfg)
    # per-step inference on the player device (host CPU when the mesh is a
    # an accelerator); blocking refresh keeps A2C on-policy
    mirror, pdev, player_key, root_key = make_param_mirror(
        cfg, dist.local_device, params, root_key, allow_async=False
    )
    gae_fn = jax.jit(
        partial(gae_op, num_steps=rollout_steps, gamma=cfg.algo.gamma, gae_lambda=cfg.algo.gae_lambda)
    )

    telem = Telemetry.setup(cfg, log_dir, rank, logger=logger, aggregator_keys=AGGREGATOR_KEYS)
    aggregator = telem.aggregator
    telem.emit(mirror.placement)
    ckpt = CheckpointManager(log_dir, keep_last=cfg.checkpoint.keep_last, enabled=rank == 0)
    guard = RunGuard.setup(cfg, ckpt, telem, log_dir)
    ckpt = guard.ckpt

    policy_steps_per_iter = num_envs * rollout_steps
    num_updates = int(cfg.algo.total_steps) // policy_steps_per_iter if not cfg.dry_run else 1
    start_iter = (state["update"] + 1) if state else 1
    policy_step = state["policy_step"] if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0
    total_batch = rollout_steps * num_envs

    obs, _ = envs.reset(seed=cfg.seed)

    def _ckpt_state():
        return {
            "params": params,
            "opt_state": opt_state,
            "update": update_iter,
            "policy_step": policy_step,
            "last_log": last_log,
            "last_checkpoint": last_checkpoint,
            "rng": root_key,
        }

    for update_iter in range(start_iter, num_updates + 1):
        telem.tick(policy_step)
        with telem.span("Time/env_interaction_time"):
            for _ in range(rollout_steps):
                device_obs = prepare_obs(obs, (), mlp_keys, num_envs)
                player_key, act_key = jax.random.split(player_key)
                actions, logprobs, values = act(mirror.current(), device_obs, act_key)
                np_actions = np.asarray(actions)
                if module.is_continuous:
                    env_actions = np_actions.reshape(num_envs, -1)
                elif isinstance(action_space, gym.spaces.MultiDiscrete):
                    env_actions = np_actions.reshape(num_envs, -1)
                else:
                    env_actions = np_actions.reshape(num_envs)
                next_obs, rewards, terminated, truncated, info = envs.step(env_actions)
                policy_step += num_envs

                rewards = np.asarray(rewards, dtype=np.float32).reshape(num_envs, 1)
                dones = np.logical_or(terminated, truncated).astype(np.float32).reshape(num_envs, 1)

                # truncation bootstrapping (reference a2c.py:250-270)
                if np.any(truncated) and "final_obs" in info:
                    final_obs = info["final_obs"]
                    trunc_idx = np.nonzero(truncated)[0]
                    stacked = {
                        k: np.stack([np.asarray(final_obs[i][k]) for i in trunc_idx]) for k in obs_keys
                    }
                    vals = np.asarray(
                        value_fn(mirror.current(), prepare_obs(stacked, (), mlp_keys, len(trunc_idx)))
                    )
                    rewards[trunc_idx] += cfg.algo.gamma * vals.reshape(-1, 1)

                step_data: Dict[str, np.ndarray] = {}
                for k in obs_keys:
                    step_data[f"obs:{k}"] = np.asarray(obs[k]).reshape(1, num_envs, *obs_space[k].shape)
                step_data["actions"] = np_actions.reshape(1, num_envs, -1).astype(np.float32)
                step_data["values"] = np.asarray(values).reshape(1, num_envs, 1)
                step_data["rewards"] = rewards.reshape(1, num_envs, 1)
                step_data["dones"] = dones.reshape(1, num_envs, 1)
                rb.add(step_data, validate_args=cfg.buffer.validate_args)
                obs = next_obs

                for ep_rew, ep_len in episode_stats(info):
                    aggregator.update("Rewards/rew_avg", ep_rew)
                    aggregator.update("Game/ep_len_avg", ep_len)

        with telem.span("Time/train_time"):
            local = rb.buffer
            next_value = value_fn(mirror.current(), prepare_obs(obs, (), mlp_keys, num_envs))
            returns, advantages = gae_fn(
                jnp.asarray(local["rewards"]),
                jnp.asarray(local["values"]),
                jnp.asarray(local["dones"]),
                next_value,
            )
            data = {k: jnp.asarray(v).reshape(total_batch, *v.shape[2:]) for k, v in local.items()}
            data["returns"] = returns.reshape(total_batch, 1)
            data["advantages"] = advantages.reshape(total_batch, 1)
            data = {k: jax.device_put(v, dist.batch_sharding) for k, v in data.items()}
            params, opt_state, metrics = update(params, opt_state, data)
            telem.record_grad_steps(1)
            mirror.refresh(params)  # blocking: next rollout acts with fresh params
            run_info.mark_steady(policy_step)

        for k, v in metrics.items():
            aggregator.update(k, np.asarray(v))  # host-sync: ok (update cadence)

        if policy_step - last_log >= cfg.metric.log_every or cfg.dry_run:
            telem.log(policy_step)
            last_log = policy_step

        if (
            cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every
        ) or cfg.dry_run or update_iter == num_updates:
            last_checkpoint = policy_step
            ckpt.save(policy_step, _ckpt_state())

        if guard.stop_reached(policy_step, int(cfg.algo.total_steps), _ckpt_state):
            break

    guard.close(policy_step, _ckpt_state)
    envs.close()
    telem.close(policy_step)
    if rank == 0 and cfg.algo.run_test:
        test_env = vectorize(
            Config({**cfg.to_dict(), "env": {**cfg.env.to_dict(), "num_envs": 1}}), cfg.seed, rank, log_dir
        ).envs[0]
        test(module, params, test_env, cfg, log_dir, logger)
    if rank == 0 and not cfg.model_manager.disabled:
        from ...utils.model_manager import register_model

        register_model(cfg, {"agent": params}, log_dir)
    if logger is not None:
        logger.close()


@register_evaluation(algorithms="a2c")
def evaluate_a2c(dist: Distributed, cfg: Config, state: Dict[str, Any]) -> None:
    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, dist.process_index)
    env = vectorize(cfg, cfg.seed, 0, log_dir).envs[0]
    root_key = dist.seed_everything(cfg.seed)
    module, params = build_agent(dist, cfg, env.observation_space, env.action_space, root_key, state["params"])
    test(module, params, env, cfg, log_dir, logger)
