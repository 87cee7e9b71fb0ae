"""DroQ — high-replay-ratio off-policy training (Template B).

Reference sheeprl/algos/droq/droq.py (436 LoC). Differences from SAC that
matter (reference train(), droq.py:31-137):
* critics use Dropout+LayerNorm and are updated `replay_ratio≈20` times per
  policy step, each gradient step with a fresh target-action sample and fresh
  dropout masks;
* the actor/alpha update happens ONCE per train call, on its own batch, and
  uses the MEAN of the Q-ensemble (droq.py:120-122), not the min.

The TPU version runs the G critic updates as one jitted `lax.scan` (fresh
PRNG folds per step per ensemble member) followed by the single actor/alpha
step, all donated.
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
from ...parallel import Distributed
from ...parallel.placement import make_param_mirror
from ...telemetry import Telemetry
from ...utils.checkpoint import CheckpointManager
from ...utils.env import episode_stats, vectorize
from ...utils.logger import get_log_dir, get_logger
from ...utils.metric import MetricAggregator
from ...utils.registry import register_algorithm, register_evaluation
from ...resilience import RunGuard
from ...utils.utils import Ratio, save_configs
from ..sac.agent import sample_actions
from ..sac.loss import critic_loss, entropy_loss, policy_loss
from ..sac.utils import AGGREGATOR_KEYS, flatten_obs, test
from .agent import build_agent


def make_train_fn(actor, critic, txs, cfg: Config, target_entropy: float):
    gamma = float(cfg.algo.gamma)
    tau = float(cfg.algo.tau)

    def critic_step(carry, inp):
        params, opt_states = carry
        batch, key = inp
        key, k_act, k_drop_t, k_drop = jax.random.split(key, 4)
        mean, log_std = actor.apply({"params": params["actor"]}, batch["next_observations"])
        next_actions, next_logprobs = sample_actions(actor, mean, log_std, k_act)
        target_q = critic.apply(
            {"params": params["target_critic"]},
            batch["next_observations"],
            next_actions,
            deterministic=False,
            rngs={"dropout": k_drop_t},
        )
        min_target = jnp.min(target_q, axis=0) - jnp.exp(params["log_alpha"]) * next_logprobs
        # bootstrap through truncation (terminated only, as in the reference)
        y = batch["rewards"] + (1.0 - batch["terminated"]) * gamma * min_target

        def qf_loss_fn(cp):
            q = critic.apply(
                {"params": cp},
                batch["observations"],
                batch["actions"],
                deterministic=False,
                rngs={"dropout": k_drop},
            )
            return critic_loss(q, jax.lax.stop_gradient(y), q.shape[0])

        qf_loss, grads = jax.value_and_grad(qf_loss_fn)(params["critic"])
        updates, opt_states["critic"] = txs["critic"].update(grads, opt_states["critic"], params["critic"])
        params["critic"] = optax.apply_updates(params["critic"], updates)
        # per-step EMA (reference droq.py:116-117)
        params["target_critic"] = jax.tree.map(
            lambda t, s: (1 - tau) * t + tau * s, params["target_critic"], params["critic"]
        )
        return (params, opt_states), qf_loss

    @partial(jax.jit, donate_argnums=(0, 1))
    def train(params, opt_states, critic_batches, actor_batch, keys, actor_key):
        (params, opt_states), qf_losses = jax.lax.scan(
            critic_step, (params, opt_states), (critic_batches, keys)
        )

        # --- single actor update on its own batch, MEAN of Q -------------
        def actor_loss_fn(ap):
            m, ls = actor.apply({"params": ap}, actor_batch["observations"])
            # one split, two independent streams: sampling the actions and
            # the critic's dropout masks must not share actor_key
            k_sample, k_drop = jax.random.split(actor_key)
            acts, logp = sample_actions(actor, m, ls, k_sample)
            q = critic.apply(
                {"params": params["critic"]},
                actor_batch["observations"],
                acts,
                deterministic=False,
                rngs={"dropout": k_drop},
            )
            mean_q = jnp.mean(q, axis=0)
            return policy_loss(jnp.exp(params["log_alpha"]), logp, mean_q), logp

        (a_loss, logp), a_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(params["actor"])
        updates, opt_states["actor"] = txs["actor"].update(a_grads, opt_states["actor"], params["actor"])
        params["actor"] = optax.apply_updates(params["actor"], updates)

        def alpha_loss_fn(log_alpha):
            return entropy_loss(log_alpha, jax.lax.stop_gradient(logp), target_entropy)

        al_loss, al_grad = jax.value_and_grad(alpha_loss_fn)(params["log_alpha"])
        updates, opt_states["alpha"] = txs["alpha"].update(al_grad, opt_states["alpha"], params["log_alpha"])
        params["log_alpha"] = optax.apply_updates(params["log_alpha"], updates)

        metrics = {
            "Loss/value_loss": jnp.mean(qf_losses),
            "Loss/policy_loss": a_loss,
            "Loss/alpha_loss": al_loss,
        }
        return params, opt_states, metrics

    return train


@register_algorithm(name="droq")
def main(dist: Distributed, cfg: Config) -> None:
    if cfg.algo.cnn_keys.encoder:
        import warnings

        warnings.warn("DroQ cannot use image observations; CNN keys are ignored")
        cfg.algo.cnn_keys.encoder = []

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

    state = None
    if cfg.checkpoint.resume_from:
        state = CheckpointManager.load(cfg.checkpoint.resume_from)
    root_key, init_key = jax.random.split(state["rng"] if state else root_key)
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
        }
    opt_states = dist.replicate(opt_states)  # all train state on the mesh before the first step

    buffer_size = int(cfg.buffer.size) if not cfg.dry_run else max(2 * num_envs, 8)
    rb = ReplayBuffer(
        buffer_size,
        num_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}") if cfg.buffer.memmap else None,
        seed=cfg.seed + 1024 * rank,
    )
    if state and cfg.buffer.checkpoint and "rb" in state:
        rb.load_state_dict(state["rb"])

    train = make_train_fn(actor, critic, txs, cfg, target_entropy)

    @jax.jit
    def act(actor_params, obs, key):
        mean, log_std = actor.apply({"params": actor_params}, obs)
        actions, _ = sample_actions(actor, mean, log_std, key)
        return actions

    telem = Telemetry.setup(cfg, log_dir, rank, logger=logger, aggregator_keys=AGGREGATOR_KEYS)
    aggregator = telem.aggregator
    ckpt = CheckpointManager(log_dir, keep_last=cfg.checkpoint.keep_last, enabled=rank == 0)
    guard = RunGuard.setup(cfg, ckpt, telem, log_dir)
    ckpt = guard.ckpt
    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if state and "ratio" in state:
        ratio.load_state_dict(state["ratio"])

    batch_size = int(cfg.algo.per_rank_batch_size) * dist.world_size
    total_steps = int(cfg.algo.total_steps) if not cfg.dry_run else num_envs
    learning_starts = int(cfg.algo.learning_starts) if not cfg.dry_run else 0
    policy_step = state["policy_step"] if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0

    # per-step inference on the player device (host CPU when the mesh is a
    # an accelerator); mirror re-syncs the actor after each train burst
    mirror, pdev, player_key, root_key = make_param_mirror(
        cfg, dist.local_device, {"actor": params["actor"]}, root_key
    )
    telem.emit(mirror.placement)

    obs, _ = envs.reset(seed=cfg.seed)
    obs_vec = flatten_obs(obs, mlp_keys, num_envs)

    def _ckpt_state():
        s = {
            "params": params,
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

    pending_metrics: list = []

    while policy_step < total_steps:
        telem.tick(policy_step)
        if guard.stop_reached(policy_step, total_steps, _ckpt_state):
            break
        with telem.span("Time/env_interaction_time"):
            if policy_step <= learning_starts:
                env_actions = np.stack([action_space.sample() for _ in range(num_envs)])
            else:
                player_key, k = jax.random.split(player_key)
                env_actions = np.asarray(
                    act(mirror.current()["actor"], obs_vec, k)
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

            rb.add(
                {
                    "observations": obs_vec.reshape(1, num_envs, -1),
                    "next_observations": real_next.reshape(1, num_envs, -1),
                    "actions": env_actions.reshape(1, num_envs, act_dim).astype(np.float32),
                    "rewards": np.asarray(rewards, np.float32).reshape(1, num_envs, 1),
                    "terminated": np.asarray(terminated, np.float32).reshape(1, num_envs, 1),
                    "dones": np.logical_or(terminated, truncated).astype(np.float32).reshape(1, num_envs, 1),
                },
                validate_args=cfg.buffer.validate_args,
            )
            obs_vec = flatten_obs(next_obs, mlp_keys, num_envs)

            for ep_rew, ep_len in episode_stats(info):
                aggregator.update("Rewards/rew_avg", ep_rew)
                aggregator.update("Game/ep_len_avg", ep_len)

        if policy_step >= learning_starts:
            g = ratio(policy_step / dist.world_size)
            if g > 0:
                with telem.span("Time/train_time"):
                    sample = rb.sample(batch_size * g)
                    mb_sharding = dist.shard_batch_axis(1)
                    critic_batches = {
                        k: jax.device_put(np.asarray(v).reshape(g, batch_size, *v.shape[2:]), mb_sharding)
                        for k, v in sample.items()
                    }
                    actor_sample = rb.sample(batch_size)
                    actor_batch = {
                        k: jax.device_put(
                            np.asarray(v).reshape(batch_size, *v.shape[2:]), dist.batch_sharding
                        )
                        for k, v in actor_sample.items()
                    }
                    root_key, sub, ak = jax.random.split(root_key, 3)
                    keys = jax.random.split(sub, g)
                    params, opt_states, metrics = train(
                        params, opt_states, critic_batches, actor_batch, keys, ak
                    )
                    mirror.refresh({"actor": params["actor"]})
                if not MetricAggregator.disabled:
                    # device refs held until the log-cadence host sync;
                    # skip entirely when metrics are off (bench legs)
                    pending_metrics.append(metrics)

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
        test_env = vectorize(
            Config({**cfg.to_dict(), "env": {**cfg.env.to_dict(), "num_envs": 1}}), cfg.seed, rank, log_dir
        ).envs[0]
        test(actor, params["actor"], test_env, cfg, log_dir, logger)
    if rank == 0 and not cfg.model_manager.disabled:
        from ...utils.model_manager import register_model

        register_model(cfg, {"actor": params["actor"], "critic": params["critic"]}, log_dir)
    if logger is not None:
        logger.close()


@register_evaluation(algorithms="droq")
def evaluate_droq(dist: Distributed, cfg: Config, state: Dict[str, Any]) -> None:
    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, dist.process_index)
    env = vectorize(cfg, cfg.seed, 0, log_dir).envs[0]
    root_key = dist.seed_everything(cfg.seed)
    actor, critic, params = build_agent(
        dist, cfg, env.observation_space, env.action_space, root_key, state["params"]
    )
    test(actor, params["actor"], env, cfg, log_dir, logger)
