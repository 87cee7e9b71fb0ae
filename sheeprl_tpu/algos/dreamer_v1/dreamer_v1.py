"""DreamerV1 — Gaussian world-model RL (Template B).

Reference sheeprl/algos/dreamer_v1/dreamer_v1.py (750 LoC). TPU-native
re-design mirroring this repo's DreamerV2/V3 implementations:

* dynamic learning (reference python loop :144-157) → `lax.scan` of the
  Gaussian RSSM step; imagination (:240-250) → second scan;
* one jitted, donated-argument gradient step updating world model, actor
  (pure dynamics-backprop: loss = -E[discount·λ], no reinforce mix) and
  critic — DV1 has no target critic;
* Normal(·,1) observation/reward/value heads; Gaussian KL with free nats
  (no balancing);
* exploration-noise player with the `expl_amount` half-life decay schedule
  (reference dreamer_v2/agent.py:499-503, shared by DV1).
"""
from __future__ import annotations

import os
from functools import partial
from typing import Any, Dict, Sequence

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from ...config import Config, instantiate
from ...data import EnvIndependentReplayBuffer, SequentialReplayBuffer
from ...distributions import Bernoulli, Independent, Normal
from ...data.device_ring import estimate_row_bytes, make_sequential_prefetcher
from ...ops.transforms import unrolled_cumprod
from ...optim import clipped
from ...parallel import Distributed
from ...parallel.placement import make_param_mirror, player_device
from ...telemetry import Telemetry
from ...utils.checkpoint import CheckpointManager
from ...utils.env import episode_stats, patch_restarted_envs, vectorize
from ...utils.logger import get_log_dir, get_logger
from ...utils.metric import MetricAggregator
from ...utils.registry import register_algorithm, register_evaluation
from ...resilience import RunGuard
from ...utils import run_info
from ...utils.utils import Ratio, save_configs
from ..dreamer_v2.dreamer_v2 import make_player as make_dreamer_player
from .agent import DV1WorldModel, build_agent, dv2_sample_actions
from .loss import actor_loss, critic_loss, reconstruction_loss
from ..dreamer_v3.utils import make_precision_applies
from .utils import (
    AGGREGATOR_KEYS,
    compute_lambda_values,
    normalize_obs,
    prepare_obs,
    test,
)


def make_train_fn(
    wm: DV1WorldModel,
    actor,
    critic,
    txs,
    cfg: Config,
    is_continuous: bool,
    actions_dim: Sequence[int],
):
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    wm_cfg = cfg.algo.world_model
    S = int(wm_cfg.stochastic_size)
    R = int(wm_cfg.recurrent_model.recurrent_state_size)
    horizon = int(cfg.algo.horizon)
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    use_continues = bool(wm_cfg.use_continues)

    # mixed precision: shared cast boundary (dreamer_v3/utils.py)
    wm_apply, actor_apply, critic_apply, *_ = make_precision_applies(cfg, wm, actor, critic)

    def one_step(params, opt_states, batch, key):
        T, B = batch["rewards"].shape[:2]
        k_dyn, k_img = jax.random.split(key, 2)
        batch_obs = normalize_obs({k: batch[k] for k in cnn_keys + mlp_keys}, cnn_keys)

        # ---------------- world model ------------------------------------
        def wm_loss_fn(wm_params):
            embedded = wm_apply(wm_params, DV1WorldModel.embed, batch_obs)  # [T, B, E]

            def dyn_step(carry, xs):
                h, z = carry
                a, e, k = xs
                h, z, post_ms, prior_ms = wm_apply(
                    wm_params, DV1WorldModel.dynamic, z, h, a, e, k
                )
                return (h, z), (h, z, post_ms[0], post_ms[1], prior_ms[0], prior_ms[1])

            keys = jax.random.split(k_dyn, T)
            _, (hs, zs, post_mean, post_std, prior_mean, prior_std) = jax.lax.scan(
                dyn_step,
                (jnp.zeros((B, R)), jnp.zeros((B, S))),
                (batch["actions"], embedded, keys),
            )
            latents = jnp.concatenate([zs, hs], axis=-1)
            recon = wm_apply(wm_params, DV1WorldModel.decode, latents)
            qo = {
                k: Independent(Normal(recon[k], 1.0), 3 if k in cnn_keys else 1)
                for k in cnn_keys + mlp_keys
            }
            qr = Independent(Normal(wm_apply(wm_params, DV1WorldModel.reward, latents), 1.0), 1)
            if use_continues:
                qc = Independent(
                    Bernoulli(logits=wm_apply(wm_params, DV1WorldModel.cont, latents)), 1
                )
                continues_targets = (1 - batch["terminated"]) * gamma
            else:
                qc = continues_targets = None
            posteriors_dist = Independent(Normal(post_mean, post_std), 1)
            priors_dist = Independent(Normal(prior_mean, prior_std), 1)
            rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = (
                reconstruction_loss(
                    qo,
                    batch_obs,
                    qr,
                    batch["rewards"],
                    posteriors_dist,
                    priors_dist,
                    float(wm_cfg.kl_free_nats),
                    float(wm_cfg.kl_regularizer),
                    qc,
                    continues_targets,
                    float(wm_cfg.continue_scale_factor),
                )
            )
            aux = {
                "zs": zs,
                "hs": hs,
                "post_entropy": jnp.mean(posteriors_dist.entropy()),
                "prior_entropy": jnp.mean(priors_dist.entropy()),
                "Loss/world_model_loss": rec_loss,
                "Loss/observation_loss": observation_loss,
                "Loss/reward_loss": reward_loss,
                "Loss/state_loss": state_loss,
                "Loss/continue_loss": continue_loss,
                "State/kl": kl,
            }
            return rec_loss, aux

        (wm_loss, wm_aux), wm_grads = jax.value_and_grad(wm_loss_fn, has_aux=True)(params["wm"])
        updates, opt_states["wm"] = txs["wm"].update(wm_grads, opt_states["wm"], params["wm"])
        params["wm"] = optax.apply_updates(params["wm"], updates)

        # ---------------- behaviour (dynamics backprop) -------------------
        imagined_prior0 = jax.lax.stop_gradient(wm_aux["zs"]).reshape(T * B, S)
        recurrent0 = jax.lax.stop_gradient(wm_aux["hs"]).reshape(T * B, R)

        def rollout(actor_params, key):
            """Imagination (reference :228-250): act on the current latent,
            step the prior, store the *post-step* latent — H rows total."""

            def img_step(carry, k):
                z, h = carry
                k_a, k_i = jax.random.split(k)
                latent = jnp.concatenate([z, h], axis=-1)
                pre = actor_apply(actor_params, jax.lax.stop_gradient(latent))
                acts, _ = dv2_sample_actions(actor, pre, k_a)
                a = jnp.concatenate(acts, axis=-1)
                z, h = wm_apply(params["wm"], DV1WorldModel.imagination, z, h, a, k_i)
                return (z, h), jnp.concatenate([z, h], axis=-1)

            keys = jax.random.split(key, horizon)
            _, latents = jax.lax.scan(img_step, (imagined_prior0, recurrent0), keys)
            return latents  # [H, T*B, S+R]

        def actor_loss_fn(actor_params):
            trajectories = rollout(actor_params, k_img)
            predicted_values = critic_apply(params["critic"], trajectories)
            predicted_rewards = wm_apply(params["wm"], DV1WorldModel.reward, trajectories)
            if use_continues:
                continues = jax.nn.sigmoid(
                    wm_apply(params["wm"], DV1WorldModel.cont, trajectories)
                )
            else:
                continues = jnp.ones_like(predicted_rewards) * gamma
            lv = compute_lambda_values(
                predicted_rewards,
                predicted_values,
                continues,
                last_values=predicted_values[-1],
                horizon=horizon,
                lmbda=lmbda,
            )
            discount = jax.lax.stop_gradient(
                unrolled_cumprod(
                    jnp.concatenate([jnp.ones_like(continues[:1]), continues[:-2]], axis=0)
                )
            )
            policy_loss = actor_loss(discount * lv)
            aux = {
                "trajectories": jax.lax.stop_gradient(trajectories),
                "lambda_values": jax.lax.stop_gradient(lv),
                "discount": discount,
            }
            return policy_loss, aux

        (policy_loss, a_aux), a_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(
            params["actor"]
        )
        updates, opt_states["actor"] = txs["actor"].update(
            a_grads, opt_states["actor"], params["actor"]
        )
        params["actor"] = optax.apply_updates(params["actor"], updates)

        # ---------------- critic ------------------------------------------
        def critic_loss_fn(critic_params):
            qv = Independent(
                Normal(critic_apply(critic_params, a_aux["trajectories"][:-1]), 1.0), 1
            )
            return critic_loss(qv, a_aux["lambda_values"], a_aux["discount"][..., 0])

        value_loss, c_grads = jax.value_and_grad(critic_loss_fn)(params["critic"])
        updates, opt_states["critic"] = txs["critic"].update(
            c_grads, opt_states["critic"], params["critic"]
        )
        params["critic"] = optax.apply_updates(params["critic"], updates)

        metrics = {
            "Loss/world_model_loss": wm_aux["Loss/world_model_loss"],
            "Loss/observation_loss": wm_aux["Loss/observation_loss"],
            "Loss/reward_loss": wm_aux["Loss/reward_loss"],
            "Loss/state_loss": wm_aux["Loss/state_loss"],
            "Loss/continue_loss": wm_aux["Loss/continue_loss"],
            "State/kl": wm_aux["State/kl"],
            "State/post_entropy": wm_aux["post_entropy"],
            "State/prior_entropy": wm_aux["prior_entropy"],
            "Loss/policy_loss": policy_loss,
            "Loss/value_loss": value_loss,
        }
        return params, opt_states, metrics

    @partial(jax.jit, donate_argnums=(0, 1))
    def train(params, opt_states, batches, keys):
        """G gradient steps in one device call: scan `one_step` over
        `batches` [G, T, B, ...] / `keys` [G]; metrics come back [G]-shaped
        (see dreamer_v3.make_train_fn for the rationale)."""

        def body(carry, xs):
            params, opt_states = carry
            batch, key = xs
            params, opt_states, metrics = one_step(params, opt_states, batch, key)
            return (params, opt_states), metrics

        (params, opt_states), metrics = jax.lax.scan(
            body, (params, opt_states), (batches, keys)
        )
        return params, opt_states, metrics

    return train


def make_player(
    wm: DV1WorldModel, actor, cfg: Config, actions_dim, is_continuous: bool, num_envs: int
):
    """Device-resident player (replaces reference PlayerDV1, agent.py:219-298).
    Identical to the DV2 player apart from the Gaussian stochastic-state
    width, so it delegates to the shared factory."""
    return make_dreamer_player(
        wm,
        actor,
        cfg,
        actions_dim,
        is_continuous,
        num_envs,
        stoch_width=int(cfg.algo.world_model.stochastic_size),
    )


@register_algorithm(name="dreamer_v1")
def main(dist: Distributed, cfg: Config) -> None:
    root_key = dist.seed_everything(cfg.seed)
    rank = dist.process_index
    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, rank)
    if rank == 0:
        save_configs(cfg, log_dir)

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

    state = None
    if cfg.checkpoint.resume_from:
        state = CheckpointManager.load(cfg.checkpoint.resume_from)
    root_key, init_key = jax.random.split(state["rng"] if state else root_key)
    wm, actor, critic, params = build_agent(
        dist, cfg, obs_space, actions_dim, is_continuous, init_key, state["params"] if state else None
    )

    txs = {
        "wm": clipped(instantiate(cfg.algo.world_model.optimizer), cfg.algo.world_model.clip_gradients),
        "actor": clipped(instantiate(cfg.algo.actor.optimizer), cfg.algo.actor.clip_gradients),
        "critic": clipped(instantiate(cfg.algo.critic.optimizer), cfg.algo.critic.clip_gradients),
    }
    if state:
        opt_states = state["opt_states"]
    else:
        opt_states = {
            "wm": txs["wm"].init(params["wm"]),
            "actor": txs["actor"].init(params["actor"]),
            "critic": txs["critic"].init(params["critic"]),
        }
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
    if state and cfg.buffer.checkpoint and "rb" in state:
        rb.load_state_dict(state["rb"])

    train = make_train_fn(wm, actor, critic, txs, cfg, is_continuous, actions_dim)
    player_init, player_step_fn, expl_amount_at = make_player(
        wm, actor, cfg, actions_dim, is_continuous, num_envs
    )
    # Actor/learner split (parallel/placement.py)
    mirror, pdev, player_key, root_key = make_param_mirror(
        cfg, dist.local_device, {"wm": params["wm"], "actor": params["actor"]}, root_key
    )

    telem = Telemetry.setup(cfg, log_dir, rank, logger=logger, aggregator_keys=AGGREGATOR_KEYS)
    aggregator = telem.aggregator
    telem.emit(mirror.placement)
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

    obs, _ = envs.reset(seed=cfg.seed)
    player_state = jax.device_put(player_init(), pdev)

    # row 0: reset obs, zero action/reward (reference :545-556 — DV1 stores no
    # is_first; its RSSM never resets mid-sequence)
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
            if policy_step <= learning_starts:
                actions_env = np.stack([action_space.sample() for _ in range(num_envs)])
                if is_continuous:
                    actions_np = actions_env.reshape(num_envs, -1).astype(np.float32)
                else:
                    oh = []
                    acts2d = actions_env.reshape(num_envs, -1)
                    for j, adim in enumerate(actions_dim):
                        oh.append(np.eye(adim, dtype=np.float32)[acts2d[:, j]])
                    actions_np = np.concatenate(oh, axis=-1)
            else:
                host_obs = prepare_obs(obs, cnn_keys, mlp_keys, num_envs)
                expl_amount = expl_amount_at(policy_step)
                aggregator.update("Params/exploration_amount", expl_amount)
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
                mirror.refresh({"wm": params["wm"], "actor": params["actor"]})
                run_info.mark_steady(policy_step, sync=lambda: jax.block_until_ready(metrics))
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
            {
                "world_model": params["wm"],
                "actor": params["actor"],
                "critic": params["critic"],
            },
            log_dir,
        )
    if logger is not None:
        logger.close()


@register_evaluation(algorithms="dreamer_v1")
def evaluate_dreamer_v1(dist: Distributed, cfg: Config, state: Dict[str, Any]) -> None:
    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, dist.process_index)
    env = vectorize(cfg, cfg.seed, 0, log_dir).envs[0]
    root_key = dist.seed_everything(cfg.seed)
    action_space = env.action_space
    is_continuous = isinstance(action_space, gym.spaces.Box)
    if is_continuous:
        actions_dim = [int(np.prod(action_space.shape))]
    elif isinstance(action_space, gym.spaces.MultiDiscrete):
        actions_dim = [int(n) for n in action_space.nvec]
    else:
        actions_dim = [int(action_space.n)]
    wm, actor, critic, params = build_agent(
        dist, cfg, env.observation_space, actions_dim, is_continuous, root_key, state["params"]
    )
    t_init, t_step, _ = make_player(wm, actor, cfg, actions_dim, is_continuous, 1)
    pdev = player_device(cfg, dist.local_device)
    t_params = jax.device_put({"wm": params["wm"], "actor": params["actor"]}, pdev)
    t_state = jax.device_put(t_init(), pdev)

    def _step(o, s, k, greedy):
        env_actions, _, s, k = t_step(t_params, o, s, k, greedy)
        return env_actions, s, k

    test(_step, t_state, env, cfg, log_dir, logger, device=pdev)
