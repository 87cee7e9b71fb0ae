"""PPO decoupled — player/trainer split (Template C).

Reference sheeprl/algos/ppo/ppo_decoupled.py (670 LoC): rank-0 player process
steps the envs and scatters rollout chunks to a DDP trainer group over
gloo/NCCL; trainers send back a flattened parameter vector
(:114-127, :294-305).

TPU-native re-design: JAX is single-controller, so the process split becomes
a **player thread + trainer main thread** in one process. The player owns the
envs and the jitted act/GAE path; the trainer owns the jitted DP update over
the full device mesh. They rendezvous once per iteration through a pair of
depth-1 queues — the same synchronous protocol as the reference's
scatter/broadcast pair, with the parameter "broadcast" reduced to handing
over the params pytree (device buffers move, nothing is copied). Env
stepping (host C code) overlaps XLA execution because both release the GIL.

Decoupling still requires ≥2 devices (cli check, reference cli.py:100-105) —
the trainer's mesh spans all of them while the player's small inference fn
runs on device 0.
"""
from __future__ import annotations

import os
import queue
import threading
from functools import partial
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np

from ...config import Config, instantiate
from ...data import ReplayBuffer
from ...ops import gae as gae_op
from ...optim import clipped
from ...parallel import Distributed
from ...parallel.placement import ParamMirror, player_device
from ...utils.checkpoint import CheckpointManager
from ...utils.env import episode_stats, vectorize
from ...telemetry import Telemetry
from ...utils.logger import get_log_dir, get_logger
from ...utils.registry import register_algorithm
from ...resilience import RunGuard
from ...utils.utils import linear_annealing, save_configs
from .agent import build_agent
from .ppo import make_act_fn, make_update_fn, make_value_fn
from .utils import AGGREGATOR_KEYS, prepare_obs, test


class _PlayerCrashed(Exception):
    pass


def _player_loop(
    dist: Distributed,
    cfg: Config,
    module,
    init_params,
    log_dir: str,
    telem: Telemetry,
    data_q: "queue.Queue",
    params_q: "queue.Queue",
    start_iter: int,
    num_updates: int,
    seed_key,
) -> None:
    """Env-stepping half (reference player(), ppo_decoupled.py:33-365)."""
    try:
        envs = vectorize(cfg, cfg.seed, 0, log_dir)
        obs_space = envs.single_observation_space
        action_space = envs.single_action_space
        num_envs = int(cfg.env.num_envs)
        cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
        mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
        obs_keys = cnn_keys + mlp_keys
        rollout_steps = int(cfg.algo.rollout_steps)
        total_batch = rollout_steps * num_envs

        act = make_act_fn(module)
        value_fn = make_value_fn(module)
        gae_fn = jax.jit(
            partial(
                gae_op,
                num_steps=rollout_steps,
                gamma=cfg.algo.gamma,
                gae_lambda=cfg.algo.gae_lambda,
            )
        )

        rb = ReplayBuffer(
            rollout_steps,
            num_envs,
            obs_keys=obs_keys,
            memmap=cfg.buffer.memmap,
            memmap_dir=os.path.join(log_dir, "memmap_buffer", "rank_0")
            if cfg.buffer.memmap
            else None,
            seed=cfg.seed,  # decoupled: one player thread owns the buffer
        )

        # per-step inference on the player device (host CPU when the mesh is
        # an accelerator); ParamMirror's defensive copy keeps the
        # trainer's donated buffers from dying under us on shared devices
        pdev = player_device(cfg, dist.local_device)
        mirror = ParamMirror(init_params, pdev)
        root_key = jax.device_put(seed_key, pdev)
        obs, _ = envs.reset(seed=cfg.seed)
        policy_step = (start_iter - 1) * num_envs * rollout_steps

        for update_iter in range(start_iter, num_updates + 1):
            with telem.span("Time/env_interaction_time"):
                for _ in range(rollout_steps):
                    device_obs = prepare_obs(obs, cnn_keys, mlp_keys, num_envs)
                    root_key, act_key = jax.random.split(root_key)
                    actions, logprobs, values = act(mirror.params, device_obs, act_key)
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
                    dones = (
                        np.logical_or(terminated, truncated).astype(np.float32).reshape(num_envs, 1)
                    )

                    if np.any(truncated) and "final_obs" in info:
                        final_obs = info["final_obs"]
                        trunc_idx = np.nonzero(truncated)[0]
                        stacked = {
                            k: np.stack([np.asarray(final_obs[i][k]) for i in trunc_idx])
                            for k in obs_keys
                        }
                        vals = np.asarray(
                            value_fn(
                                mirror.params,
                                prepare_obs(stacked, cnn_keys, mlp_keys, len(trunc_idx)),
                            )
                        )
                        rewards[trunc_idx] += cfg.algo.gamma * vals.reshape(-1, 1)

                    step_data: Dict[str, np.ndarray] = {}
                    for k in obs_keys:
                        step_data[f"obs:{k}"] = np.asarray(obs[k]).reshape(
                            1, num_envs, *obs_space[k].shape
                        )
                    step_data["actions"] = np_actions.reshape(1, num_envs, -1).astype(np.float32)
                    step_data["logprobs"] = np.asarray(logprobs).reshape(1, num_envs, 1)
                    step_data["values"] = np.asarray(values).reshape(1, num_envs, 1)
                    step_data["rewards"] = rewards.reshape(1, num_envs, 1)
                    step_data["dones"] = dones.reshape(1, num_envs, 1)
                    rb.add(step_data, validate_args=cfg.buffer.validate_args)
                    obs = next_obs

                    for ep_rew, ep_len in episode_stats(info):
                        telem.update("Rewards/rew_avg", ep_rew)
                        telem.update("Game/ep_len_avg", ep_len)

                local = rb.buffer
                next_value = value_fn(mirror.params, prepare_obs(obs, cnn_keys, mlp_keys, num_envs))
                returns, advantages = gae_fn(
                    jnp.asarray(local["rewards"]),
                    jnp.asarray(local["values"]),
                    jnp.asarray(local["dones"]),
                    next_value,
                )
                data = {
                    k: np.asarray(v).reshape(total_batch, *v.shape[2:]) for k, v in local.items()
                }
                data["returns"] = np.asarray(returns).reshape(total_batch, 1)
                data["advantages"] = np.asarray(advantages).reshape(total_batch, 1)

            # hand the rollout to the trainer, wait for the new params
            # (reference scatter :294-299 + param broadcast :302-305)
            data_q.put((update_iter, policy_step, data))
            new_params = params_q.get()
            if new_params is None:  # trainer crashed
                break
            mirror.refresh(new_params)

        envs.close()
        try:  # nowait: the trainer may have left an unconsumed rollout behind
            data_q.put_nowait(None)  # rollout source exhausted
        except queue.Full:
            pass
    except BaseException as e:  # surface crashes to the trainer
        try:
            data_q.put(e, timeout=30)
        except queue.Full:
            pass
        raise


@register_algorithm(name="ppo_decoupled", decoupled=True)
def main(dist: Distributed, cfg: Config) -> None:
    root_key = dist.seed_everything(cfg.seed)
    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, 0)
    save_configs(cfg, log_dir)

    # spaces probed without stepping (the player owns the real envs)
    probe = vectorize(
        Config({**cfg.to_dict(), "env": {**cfg.env.to_dict(), "num_envs": 1}}), cfg.seed, 0, None
    )
    obs_space = probe.single_observation_space
    action_space = probe.single_action_space
    probe.close()

    state = None
    if cfg.checkpoint.resume_from:
        state = CheckpointManager.load(cfg.checkpoint.resume_from)
    root_key, init_key, player_key = jax.random.split(state["rng"] if state else root_key, 3)
    module, params = build_agent(
        dist, cfg, obs_space, action_space, init_key, state["params"] if state else None
    )

    tx = clipped(instantiate(cfg.algo.optimizer), cfg.algo.get("max_grad_norm", 0.0))
    opt_state = dist.replicate(state["opt_state"] if state else tx.init(params))

    rollout_steps = int(cfg.algo.rollout_steps)
    num_envs = int(cfg.env.num_envs)
    total_batch = rollout_steps * num_envs
    mb_size = int(cfg.algo.per_rank_batch_size) * dist.world_size
    if total_batch % mb_size != 0:
        raise ValueError(
            f"rollout_steps*num_envs ({total_batch}) must be divisible by "
            f"per_rank_batch_size*world_size ({mb_size})"
        )
    num_minibatches = total_batch // mb_size
    update = make_update_fn(module, tx, cfg, num_minibatches, mb_size)

    telem = Telemetry.setup(cfg, log_dir, 0, logger=logger, aggregator_keys=AGGREGATOR_KEYS)
    aggregator = telem.aggregator
    ckpt = CheckpointManager(log_dir, keep_last=cfg.checkpoint.keep_last, enabled=True)
    guard = RunGuard.setup(cfg, ckpt, telem, log_dir)
    ckpt = guard.ckpt

    policy_steps_per_iter = num_envs * rollout_steps
    num_updates = int(cfg.algo.total_steps) // policy_steps_per_iter if not cfg.dry_run else 1
    start_iter = (state["update"] + 1) if state else 1
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0

    data_q: "queue.Queue" = queue.Queue(maxsize=1)
    params_q: "queue.Queue" = queue.Queue(maxsize=1)
    player = threading.Thread(
        target=_player_loop,
        name="ppo-player",
        args=(
            dist, cfg, module, params, log_dir, telem, data_q, params_q,
            start_iter, num_updates, player_key,
        ),
        daemon=True,
    )
    player.start()

    policy_step = 0

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

    try:
        for update_iter in range(start_iter, num_updates + 1):
            # preemption-aware wait: a SIGTERM (or watchdog escalation)
            # unparks the trainer even if the player thread is dead
            item = guard.wait(data_q)
            if item is None:
                break
            if isinstance(item, BaseException):
                raise _PlayerCrashed("player thread crashed") from item
            _, policy_step, data = item
            telem.tick(policy_step)

            with telem.span("Time/train_time"):
                device_data = {
                    k: jax.device_put(v, dist.batch_sharding) for k, v in data.items()
                }
                frac = 1.0
                if cfg.algo.anneal_lr:
                    frac = 1.0 - (update_iter - 1) / max(num_updates, 1)
                coefs = {
                    "clip_coef": jnp.asarray(
                        linear_annealing(cfg.algo.clip_coef, update_iter - 1, num_updates)
                        if cfg.algo.anneal_clip_coef
                        else cfg.algo.clip_coef,
                        jnp.float32,
                    ),
                    "ent_coef": jnp.asarray(
                        linear_annealing(cfg.algo.ent_coef, update_iter - 1, num_updates)
                        if cfg.algo.anneal_ent_coef
                        else cfg.algo.ent_coef,
                        jnp.float32,
                    ),
                    "vf_coef": jnp.asarray(cfg.algo.vf_coef, jnp.float32),
                    "lr_frac": jnp.asarray(frac, jnp.float32),
                }
                root_key, up_key = jax.random.split(root_key)
                params, opt_state, metrics = update(params, opt_state, device_data, coefs, up_key)
                telem.record_grad_steps(num_minibatches * int(cfg.algo.update_epochs))

            # metrics / logging / checkpoint run while the player is blocked
            # on params_q.get() (the span tracker is thread-safe regardless)
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

            # wall cap BEFORE releasing the player: it is still parked in
            # params_q.get(), so the finally-block sentinel lands on an empty
            # queue and the player exits cleanly (and the shared state the
            # checkpoint snapshots is quiescent)
            if guard.stop_reached(policy_step, int(cfg.algo.total_steps), _ckpt_state):
                break
            params_q.put(params)
    finally:
        # unblock the player whatever happened
        try:
            params_q.put_nowait(None)
        except queue.Full:
            pass
    player.join(timeout=60)
    guard.close(policy_step, _ckpt_state)
    telem.close(policy_step)

    if cfg.algo.run_test:
        test_env = vectorize(
            Config({**cfg.to_dict(), "env": {**cfg.env.to_dict(), "num_envs": 1}}),
            cfg.seed,
            0,
            log_dir,
        ).envs[0]
        test(module, params, test_env, cfg, log_dir, logger)
    if not cfg.model_manager.disabled:
        from ...utils.model_manager import register_model

        register_model(cfg, {"agent": params}, log_dir)
    if logger is not None:
        logger.close()
