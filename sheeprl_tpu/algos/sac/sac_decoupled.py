"""SAC decoupled — player/trainer split (Template C).

Reference sheeprl/algos/sac/sac_decoupled.py (588 LoC): the rank-0 player
owns the replay buffer, samples `G·B·(world-1)` transitions per iteration
and scatters chunks to the DDP trainer group, which sends back flattened
parameters (:230-265).

TPU-native re-design (same shape as ppo_decoupled): a player thread owns the
envs + replay buffer and the jitted act fn; the trainer main thread runs the
scanned G-step SAC update over the device mesh. Per iteration with pending
gradient steps they exchange (batch stack, params) through depth-1 queues —
the queue handoff replaces the scatter_object_list/broadcast pair.
"""
from __future__ import annotations

import os
import queue
import threading
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np

from ...config import Config, instantiate
from ...data import ReplayBuffer
from ...parallel import Distributed
from ...parallel.placement import ParamMirror, player_device
from ...utils.checkpoint import CheckpointManager
from ...utils.env import episode_stats, vectorize
from ...telemetry import Telemetry
from ...utils.logger import get_log_dir, get_logger
from ...utils.registry import register_algorithm
from ...resilience import RunGuard
from ...utils.utils import Ratio, save_configs
from .agent import build_agent, sample_actions
from .sac import make_train_fn
from .utils import AGGREGATOR_KEYS, flatten_obs, test


class _PlayerCrashed(Exception):
    pass


def _player_loop(
    cfg: Config,
    actor,
    init_actor_params,
    log_dir: str,
    telem: Telemetry,
    data_q: "queue.Queue",
    params_q: "queue.Queue",
    batch_size: int,
    world_size: int,
    state,
    seed_key,
    guard: RunGuard,
) -> None:
    """Env stepping + buffer ownership (reference player(), :53-338)."""
    try:
        envs = vectorize(cfg, cfg.seed, 0, log_dir)
        action_space = envs.single_action_space
        num_envs = int(cfg.env.num_envs)
        mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
        act_dim = int(np.prod(action_space.shape))

        @jax.jit
        def act(actor_params, obs, key):
            mean, log_std = actor.apply({"params": actor_params}, obs)
            actions, _ = sample_actions(actor, mean, log_std, key)
            return actions

        buffer_size = int(cfg.buffer.size) if not cfg.dry_run else max(2 * num_envs, 8)
        rb = ReplayBuffer(
            buffer_size,
            num_envs,
            memmap=cfg.buffer.memmap,
            memmap_dir=os.path.join(log_dir, "memmap_buffer", "rank_0")
            if cfg.buffer.memmap
            else None,
            seed=cfg.seed,  # decoupled: one player thread owns the buffer
        )
        if state and cfg.buffer.checkpoint and "rb" in state:
            rb.load_state_dict(state["rb"])

        ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
        if state and "ratio" in state:
            ratio.load_state_dict(state["ratio"])

        # per-step inference on the player device (host CPU when the mesh is
        # an accelerator); ParamMirror's defensive copy keeps the
        # trainer's donated buffers from dying under us on shared devices
        pdev = player_device(cfg)
        mirror = ParamMirror(init_actor_params, pdev)
        root_key = jax.device_put(seed_key, pdev)
        total_steps = int(cfg.algo.total_steps) if not cfg.dry_run else num_envs
        learning_starts = int(cfg.algo.learning_starts) if not cfg.dry_run else 0
        policy_step = state["policy_step"] if state else 0

        obs, _ = envs.reset(seed=cfg.seed)
        obs_vec = flatten_obs(obs, mlp_keys, num_envs)

        while policy_step < total_steps:
            # the wall cap AND preemption drain must hold during warmup
            # too: before learning_starts the trainer is parked in
            # data_q.get() and its own check never runs, so an uncapped
            # warmup would overshoot the budget (the shared guard makes both
            # sides agree on one clock/flag); save=False — the final
            # checkpoint belongs to the trainer after the join below
            if guard.stop_reached(policy_step, total_steps, None, save=False):
                break
            with telem.span("Time/env_interaction_time"):
                if policy_step <= learning_starts:
                    env_actions = np.stack([action_space.sample() for _ in range(num_envs)])
                else:
                    root_key, k = jax.random.split(root_key)
                    env_actions = np.asarray(
                        act(mirror.params, obs_vec, k)
                    ).reshape(num_envs, act_dim)
                next_obs, rewards, terminated, truncated, info = envs.step(env_actions)
                policy_step += num_envs

                real_next = flatten_obs(next_obs, mlp_keys, num_envs).copy()
                if "final_obs" in info:
                    for i, fo in enumerate(info["final_obs"]):
                        if fo is not None:
                            real_next[i] = np.concatenate(
                                [np.asarray(fo[k], np.float32).reshape(-1) for k in mlp_keys]
                            )

                step_data = {
                    "observations": obs_vec.reshape(1, num_envs, -1),
                    "next_observations": real_next.reshape(1, num_envs, -1),
                    "actions": env_actions.reshape(1, num_envs, act_dim).astype(np.float32),
                    "rewards": np.asarray(rewards, np.float32).reshape(1, num_envs, 1),
                    "terminated": np.asarray(terminated, np.float32).reshape(1, num_envs, 1),
                    "dones": np.logical_or(terminated, truncated)
                    .astype(np.float32)
                    .reshape(1, num_envs, 1),
                }
                rb.add(step_data, validate_args=cfg.buffer.validate_args)
                obs_vec = flatten_obs(next_obs, mlp_keys, num_envs)

                for ep_rew, ep_len in episode_stats(info):
                    telem.update("Rewards/rew_avg", ep_rew)
                    telem.update("Game/ep_len_avg", ep_len)

            if policy_step >= learning_starts:
                per_rank_gradient_steps = ratio(policy_step / world_size)
                if per_rank_gradient_steps > 0:
                    # sample once, stack [G, B, ...] (reference :243-258)
                    sample = rb.sample(
                        batch_size * per_rank_gradient_steps, sample_next_obs=False, n_samples=1
                    )
                    batches = {
                        k: np.asarray(v).reshape(
                            per_rank_gradient_steps, batch_size, *v.shape[2:]
                        )
                        for k, v in sample.items()
                    }
                    data_q.put(
                        (policy_step, per_rank_gradient_steps, batches, ratio.state_dict(), rb)
                    )
                    new_actor_params = params_q.get()
                    if new_actor_params is None:
                        break
                    mirror.refresh(new_actor_params)

        envs.close()
        try:  # nowait: the trainer may have left an unconsumed batch behind
            data_q.put_nowait(None)
        except queue.Full:
            pass
    except BaseException as e:
        try:
            data_q.put(e, timeout=30)
        except queue.Full:
            pass
        raise


@register_algorithm(name="sac_decoupled", decoupled=True)
def main(dist: Distributed, cfg: Config) -> None:
    root_key = dist.seed_everything(cfg.seed)
    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, 0)
    save_configs(cfg, log_dir)

    probe = vectorize(
        Config({**cfg.to_dict(), "env": {**cfg.env.to_dict(), "num_envs": 1}}), cfg.seed, 0, None
    )
    obs_space = probe.single_observation_space
    action_space = probe.single_action_space
    probe.close()
    if not isinstance(action_space, gym.spaces.Box):
        raise RuntimeError("SAC requires a continuous (Box) action space")

    state = None
    if cfg.checkpoint.resume_from:
        state = CheckpointManager.load(cfg.checkpoint.resume_from)
    root_key, init_key, player_key = jax.random.split(state["rng"] if state else root_key, 3)
    actor, critic, params = build_agent(
        dist, cfg, obs_space, action_space, init_key, state["params"] if state else None
    )
    act_dim = int(np.prod(action_space.shape))
    target_entropy = -act_dim

    txs = {
        "actor": instantiate(cfg.algo.actor.optimizer),
        "critic": instantiate(cfg.algo.critic.optimizer),
        "alpha": instantiate(cfg.algo.alpha.optimizer),
    }
    if state:
        opt_states = state["opt_states"]
    else:
        opt_states = {
            "actor": txs["actor"].init(params["actor"]),
            "critic": txs["critic"].init(params["critic"]),
            "alpha": txs["alpha"].init(params["log_alpha"]),
            "step": jnp.zeros((), jnp.int32),
        }
    opt_states = dist.replicate(opt_states)  # all train state on the mesh before the first step

    train = make_train_fn(actor, critic, txs, cfg, target_entropy)
    batch_size = int(cfg.algo.per_rank_batch_size) * dist.world_size

    telem = Telemetry.setup(cfg, log_dir, 0, logger=logger, aggregator_keys=AGGREGATOR_KEYS)
    aggregator = telem.aggregator
    ckpt = CheckpointManager(log_dir, keep_last=cfg.checkpoint.keep_last, enabled=True)
    guard = RunGuard.setup(cfg, ckpt, telem, log_dir)
    ckpt = guard.ckpt
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0
    cumulative_grad_steps = state["cumulative_grad_steps"] if state else 0

    data_q: "queue.Queue" = queue.Queue(maxsize=1)
    params_q: "queue.Queue" = queue.Queue(maxsize=1)
    player = threading.Thread(
        target=_player_loop,
        name="sac-player",
        args=(
            cfg, actor, params["actor"], log_dir, telem, data_q, params_q,
            batch_size, dist.world_size, state, player_key, guard,
        ),
        daemon=True,
    )
    player.start()

    policy_step = 0
    rb = None
    ratio_state = None

    def _ckpt_state():
        s = {
            "params": params,
            "opt_states": opt_states,
            "ratio": ratio_state,
            "policy_step": policy_step,
            "last_log": last_log,
            "last_checkpoint": last_checkpoint,
            "cumulative_grad_steps": cumulative_grad_steps,
            "rng": root_key,
        }
        if cfg.buffer.checkpoint and rb is not None:
            s["rb"] = rb.checkpoint_state_dict()
        return s

    try:
        while True:
            # preemption-aware wait: a SIGTERM (or watchdog escalation)
            # unparks the trainer even if the player thread is dead
            item = guard.wait(data_q)
            if item is None:
                break
            if isinstance(item, BaseException):
                raise _PlayerCrashed("player thread crashed") from item
            policy_step, G, batches, ratio_state, rb = item
            telem.tick(policy_step)

            with telem.span("Time/train_time"):
                mb_sharding = dist.shard_batch_axis(1)
                device_batches = {
                    k: jax.device_put(v, mb_sharding) for k, v in batches.items()
                }
                root_key, sub = jax.random.split(root_key)
                keys = jax.random.split(sub, G)
                params, opt_states, metrics = train(params, opt_states, device_batches, keys)
                telem.record_grad_steps(G)
                cumulative_grad_steps += G

            # metrics / logging / checkpoint happen HERE, while the player is
            # still blocked on params_q.get(): the player-owned buffer is
            # quiescent, so snapshots are consistent (no torn rb.state_dict;
            # the span tracker is thread-safe regardless)
            for k, v in metrics.items():
                aggregator.update(k, np.asarray(v))  # host-sync: ok (trainer-iteration cadence)

            if policy_step - last_log >= cfg.metric.log_every or cfg.dry_run:
                telem.log(
                    policy_step,
                    extra_metrics={"Params/replay_ratio": cumulative_grad_steps / policy_step}
                    if policy_step > 0
                    else None,
                )
                last_log = policy_step

            if (
                cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every
            ) or cfg.dry_run:
                last_checkpoint = policy_step
                ckpt.save(policy_step, _ckpt_state())

            # wall cap BEFORE releasing the player: it is still parked in
            # params_q.get(), so the finally-block sentinel lands on an empty
            # queue and the player exits cleanly; the final save happens in
            # the save_last tail below, after the player thread has joined
            if guard.stop_reached(policy_step, int(cfg.algo.total_steps), _ckpt_state, save=False):
                break
            params_q.put(params["actor"])
    finally:
        try:
            params_q.put_nowait(None)
        except queue.Full:
            pass
    player.join(timeout=60)

    # final checkpoint (reference :322-338 on_checkpoint_player save_last);
    # runs after player.join, so the buffer snapshot is quiescent
    if cfg.checkpoint.save_last:
        ckpt.save(policy_step, _ckpt_state())
    guard.close(policy_step, _ckpt_state)
    telem.close(policy_step)

    if cfg.algo.run_test:
        test_env = vectorize(
            Config({**cfg.to_dict(), "env": {**cfg.env.to_dict(), "num_envs": 1}}),
            cfg.seed,
            0,
            log_dir,
        ).envs[0]
        test(actor, params["actor"], test_env, cfg, log_dir, logger)
    if not cfg.model_manager.disabled:
        from ...utils.model_manager import register_model

        register_model(cfg, {"actor": params["actor"], "critic": params["critic"]}, log_dir)
    if logger is not None:
        logger.close()
