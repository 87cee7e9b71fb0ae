"""Plan2Explore-DV1, few-shot finetuning phase.

Reference sheeprl/algos/p2e_dv1/p2e_dv1_finetuning.py (441 LoC): load the
exploration checkpoint, keep collecting with the exploration actor until
`learning_starts`, then switch the player to the task actor (reference
:330-331) and continue training world model + task actor/critic with the
plain DreamerV1 update. The exploration→finetuning config surgery (env keys
copied from the exploration run's config) happens in the CLI
(reference cli.py:117-148 → sheeprl_tpu/cli.py run_algorithm).
"""
from __future__ import annotations

import os
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np

from ...config import Config, instantiate
from ...data import EnvIndependentReplayBuffer, SequentialReplayBuffer
from ...optim import clipped
from ...data.device_ring import estimate_row_bytes, make_sequential_prefetcher
from ...parallel import Distributed
from ...parallel.placement import make_param_mirror
from ...telemetry import Telemetry
from ...utils.checkpoint import CheckpointManager
from ...utils.env import episode_stats, patch_restarted_envs, vectorize
from ...utils.logger import get_log_dir, get_logger
from ...utils.metric import MetricAggregator
from ...utils.registry import register_algorithm
from ...resilience import RunGuard
from ...utils.utils import Ratio, save_configs
from ..dreamer_v1.agent import build_agent as dv1_build_agent
from ..dreamer_v1.dreamer_v1 import make_player, make_train_fn
from ..dreamer_v1.utils import AGGREGATOR_KEYS as _DV1_KEYS, prepare_obs, test  # noqa: F401

# finetuning logs the per-actor exploration amount (exp config asks for both)
AGGREGATOR_KEYS = _DV1_KEYS | {
    "Params/exploration_amount_task",
    "Params/exploration_amount_exploration",
}
MODELS_TO_REGISTER = {"world_model", "actor", "critic"}


@register_algorithm(name="p2e_dv1_finetuning", requires_exploration_cfg=True)
def main(dist: Distributed, cfg: Config, exploration_cfg: Config) -> None:
    # Finetuning inherits the exploration run's architecture/env settings
    # (reference p2e_dv1_finetuning.py:50-71)
    for k in (
        "gamma", "lmbda", "horizon", "dense_units", "mlp_layers", "dense_act", "cnn_act",
        "world_model", "actor", "critic", "cnn_keys", "mlp_keys",
    ):
        if exploration_cfg.select(f"algo.{k}") is not None:
            cfg.set_path(f"algo.{k}", exploration_cfg.select(f"algo.{k}"))

    root_key = dist.seed_everything(cfg.seed)
    rank = dist.process_index
    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, rank)
    if rank == 0:
        save_configs(cfg, log_dir)

    resume = bool(cfg.checkpoint.resume_from)
    if resume:
        state = CheckpointManager.load(cfg.checkpoint.resume_from)
        params_in = state["params"]
        actor_exploration_params = state["actor_exploration"]
    else:
        state = None
        explo_state = CheckpointManager.load(cfg.checkpoint.exploration_ckpt_path)
        params_in = {
            "wm": explo_state["params"]["wm"],
            "actor": explo_state["params"]["actor_task"],
            "critic": explo_state["params"]["critic_task"],
        }
        actor_exploration_params = explo_state["params"]["actor_exploration"]

    # crash-prone suites restart in place; the loop patches the buffer via
    # patch_restarted_envs (reference dreamer_v3.py:385-399)
    envs = vectorize(cfg, cfg.seed, rank, log_dir, restart_handled_by_loop=True)
    obs_space = envs.single_observation_space
    action_space = envs.single_action_space
    num_envs = int(cfg.env.num_envs)
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys

    is_continuous = isinstance(action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(action_space, gym.spaces.MultiDiscrete)
    if is_continuous:
        actions_dim = [int(np.prod(action_space.shape))]
    elif is_multidiscrete:
        actions_dim = [int(n) for n in action_space.nvec]
    else:
        actions_dim = [int(action_space.n)]
    act_total = int(sum(actions_dim))

    root_key, init_key = jax.random.split(root_key)
    wm, actor, critic, params = dv1_build_agent(
        dist, cfg, obs_space, actions_dim, is_continuous, init_key, params_in
    )
    actor_exploration_params = dist.replicate(actor_exploration_params)

    txs = {
        "wm": clipped(instantiate(cfg.algo.world_model.optimizer), cfg.algo.world_model.clip_gradients),
        "actor": clipped(instantiate(cfg.algo.actor.optimizer), cfg.algo.actor.clip_gradients),
        "critic": clipped(instantiate(cfg.algo.critic.optimizer), cfg.algo.critic.clip_gradients),
    }
    if state:
        opt_states = state["opt_states"]
    else:
        opt_states = {k: txs[k].init(params[k]) for k in txs}
    opt_states = dist.replicate(opt_states)  # all train state on the mesh before the first step

    seq_len = int(cfg.algo.per_rank_sequence_length)
    buffer_size = int(cfg.buffer.size) if not cfg.dry_run else max(4 * seq_len, 64)
    rb = EnvIndependentReplayBuffer(
        buffer_size,
        n_envs=num_envs,
        obs_keys=obs_keys,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}")
        if cfg.buffer.memmap
        else None,
        buffer_cls=SequentialReplayBuffer,
        seed=cfg.seed + 1024 * rank,
    )
    if resume and cfg.buffer.checkpoint and "rb" in state:
        rb.load_state_dict(state["rb"])
    elif not resume and cfg.select("buffer.load_from_exploration") and "rb" in explo_state:
        rb.load_state_dict(explo_state["rb"])

    train = make_train_fn(wm, actor, critic, txs, cfg, is_continuous, actions_dim)
    player_init, player_step_fn, expl_amount_at = make_player(
        wm, actor, cfg, actions_dim, is_continuous, num_envs
    )

    telem = Telemetry.setup(cfg, log_dir, rank, logger=logger, aggregator_keys=AGGREGATOR_KEYS)
    aggregator = telem.aggregator
    ckpt = CheckpointManager(log_dir, keep_last=cfg.checkpoint.keep_last, enabled=rank == 0)
    guard = RunGuard.setup(cfg, ckpt, telem, log_dir)
    ckpt = guard.ckpt
    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if state and "ratio" in state:
        ratio.load_state_dict(state["ratio"])

    batch_size = int(cfg.algo.per_rank_batch_size) * dist.world_size
    total_steps = int(cfg.algo.total_steps) if not cfg.dry_run else 4 * num_envs
    learning_starts = int(cfg.algo.learning_starts) if not cfg.dry_run else 0
    policy_step = state["policy_step"] if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0
    clip_rewards_fn = (lambda r: np.tanh(r)) if cfg.env.clip_rewards else (lambda r: r)
    actor_type = str(cfg.algo.player.actor_type)

    def _host_sample(g):
        # cnn obs stay uint8 (device-side normalize casts them); the rest f32
        s = rb.sample(batch_size, sequence_length=seq_len, n_samples=g)
        return {
            k: np.asarray(v) if k in cnn_keys else np.asarray(v, np.float32)
            for k, v in s.items()
        }

    prefetch = make_sequential_prefetcher(
        cfg,
        dist,
        rb,
        batch_size,
        seq_len,
        cnn_keys=cnn_keys,
        host_sample_fn=_host_sample,
        row_bytes_hint=estimate_row_bytes(obs_space, sum(actions_dim)),
        emit=telem.emit,
    )
    pending_metrics: list = []

    def _sp():
        if actor_type == "task":
            return {"wm": params["wm"], "actor": params["actor"]}
        return {"wm": params["wm"], "actor": actor_exploration_params}

    # Actor/learner split (parallel/placement.py): see dreamer_v3.py
    mirror, pdev, player_key, root_key = make_param_mirror(cfg, dist.local_device, _sp(), root_key)
    telem.emit(mirror.placement)

    obs, _ = envs.reset(seed=cfg.seed)
    player_state = jax.device_put(player_init(), pdev)

    step_data: Dict[str, np.ndarray] = {}
    for k in obs_keys:
        step_data[k] = np.asarray(obs[k])[np.newaxis]
    step_data["actions"] = np.zeros((1, num_envs, act_total), np.float32)
    step_data["rewards"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["terminated"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["truncated"] = np.zeros((1, num_envs, 1), np.float32)
    rb.add(step_data)

    def _ckpt_state():
        s = {
            "params": params,
            "actor_exploration": actor_exploration_params,
            "opt_states": opt_states,
            "ratio": ratio.state_dict(),
            "policy_step": policy_step,
            "last_log": last_log,
            "last_checkpoint": last_checkpoint,
            "rng": root_key,
        }
        if cfg.buffer.checkpoint:
            s["rb"] = rb.checkpoint_state_dict()
        return s

    while policy_step < total_steps:
        telem.tick(policy_step)
        if guard.stop_reached(policy_step, total_steps, _ckpt_state):
            break
        with telem.span("Time/env_interaction_time"):
            # the prefill uses the exploration policy; once learning starts,
            # the task actor takes over (reference :330-331)
            if policy_step >= learning_starts and actor_type != "task":
                actor_type = "task"
                mirror.refresh(_sp())
            host_obs = prepare_obs(obs, cnn_keys, mlp_keys, num_envs)
            expl_amount = expl_amount_at(policy_step)
            aggregator.update(f"Params/exploration_amount_{actor_type}", expl_amount)
            env_actions, actions_cat, player_state, player_key = player_step_fn(
                mirror.current(), host_obs, player_state, player_key, expl_amount=expl_amount
            )
            actions_np = np.asarray(actions_cat)
            actions_env = np.asarray(env_actions)
            if is_continuous:
                actions_env = actions_env.reshape(num_envs, -1)
            elif not is_multidiscrete:
                actions_env = actions_env.reshape(num_envs)

            next_obs, rewards, terminated, truncated, info = envs.step(actions_env)
            policy_step += num_envs
            dones = np.logical_or(terminated, truncated)

            for ep_rew, ep_len in episode_stats(info):
                aggregator.update("Rewards/rew_avg", ep_rew)
                aggregator.update("Game/ep_len_avg", ep_len)

            real_next_obs = {k: np.asarray(next_obs[k]).copy() for k in obs_keys}
            if "final_obs" in info:
                for i, fo in enumerate(info["final_obs"]):
                    if fo is not None:
                        for k in obs_keys:
                            real_next_obs[k][i] = np.asarray(fo[k])

            for k in obs_keys:
                step_data[k] = real_next_obs[k][np.newaxis]
            step_data["terminated"] = np.asarray(terminated, np.float32).reshape(1, num_envs, 1)
            step_data["truncated"] = np.asarray(truncated, np.float32).reshape(1, num_envs, 1)
            step_data["actions"] = actions_np.reshape(1, num_envs, -1)
            step_data["rewards"] = clip_rewards_fn(
                np.asarray(rewards, np.float32).reshape(1, num_envs, 1)
            )

            # in-flight env restart → truncation boundary + fresh recurrent
            # state (reference dreamer_v3.py:595-608 / patch_restarted_envs)
            restarted = patch_restarted_envs(info, dones, rb, step_data)
            if restarted is not None:
                player_state = player_init(restarted, player_state)
            rb.add(step_data)

            dones_idxes = np.nonzero(dones)[0].tolist()
            if dones_idxes:
                mask = np.zeros((num_envs,), bool)
                mask[dones_idxes] = True
                player_state = player_init(mask, player_state)

            obs = next_obs

        if policy_step >= learning_starts:
            per_rank_gradient_steps = ratio(policy_step / dist.world_size)
            telem.record_grad_steps(per_rank_gradient_steps)
            if per_rank_gradient_steps > 0:
                with telem.span("Time/train_time"):
                    batches = prefetch.take(per_rank_gradient_steps)  # [G, T, B, ...]
                    root_key, sub = jax.random.split(root_key)
                    params, opt_states, metrics = train(
                        params,
                        opt_states,
                        batches,
                        jax.random.split(sub, per_rank_gradient_steps),
                    )
                if not MetricAggregator.disabled:
                    # device refs held until the log-cadence host sync;
                    # skip entirely when metrics are off (bench legs)
                    pending_metrics.append(metrics)
                mirror.refresh(_sp())
            if policy_step < total_steps:
                prefetch.stage(ratio.peek((policy_step + num_envs) / dist.world_size))

        if policy_step - last_log >= cfg.metric.log_every or cfg.dry_run:
            for m in pending_metrics:  # host-sync deferred to log cadence
                for k, v in m.items():
                    aggregator.update(k, np.asarray(v))
            pending_metrics.clear()
            telem.log(policy_step)
            last_log = policy_step

        if (
            cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every
        ) or cfg.dry_run or policy_step >= total_steps:
            last_checkpoint = policy_step
            ckpt.save(policy_step, _ckpt_state())

    guard.close(policy_step, _ckpt_state)
    envs.close()
    telem.close(policy_step)
    if rank == 0 and cfg.algo.run_test:
        test_cfg = Config({**cfg.to_dict(), "env": {**cfg.env.to_dict(), "num_envs": 1}})
        test_env = vectorize(test_cfg, cfg.seed, rank, log_dir).envs[0]
        t_init, t_step, _ = make_player(wm, actor, cfg, actions_dim, is_continuous, 1)
        t_params = jax.device_put({"wm": params["wm"], "actor": params["actor"]}, pdev)
        t_state = jax.device_put(t_init(), pdev)

        def _step(o, s, k, greedy):
            env_actions, _, s, k = t_step(t_params, o, s, k, greedy)
            return env_actions, s, k

        test(_step, t_state, test_env, cfg, log_dir, logger, device=pdev)
    if rank == 0 and not cfg.model_manager.disabled:
        from ...utils.model_manager import register_model

        register_model(
            cfg,
            {"world_model": params["wm"], "actor": params["actor"], "critic": params["critic"]},
            log_dir,
        )
    if logger is not None:
        logger.close()
