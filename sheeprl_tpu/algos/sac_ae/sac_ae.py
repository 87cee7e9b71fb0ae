"""SAC-AE — off-policy pixel SAC + autoencoder (Template B).

Reference sheeprl/algos/sac_ae/sac_ae.py (502 LoC). Per gradient step
(reference train() :35-120): critic update (encoder+Q, shared grads) →
EMA targets every `critic.per_rank_target_network_update_freq` → actor+alpha
every `actor.per_rank_update_freq` (conv features detached) → decoder+encoder
reconstruction update every `decoder.per_rank_update_freq` with a 5-bit
preprocessed image target and an L2 latent penalty.

All G gradient steps of an iteration run as one jitted `lax.scan`.
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
from ...data.device_ring import estimate_row_bytes, make_uniform_prefetcher
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
from ..sac.loss import critic_loss, entropy_loss, policy_loss
from .agent import build_agent
from .utils import AGGREGATOR_KEYS, preprocess_obs, prepare_obs_np, sample_actions_features, test


def make_train_fn(encoder, decoder, qs, actor, txs, cfg: Config, target_entropy: float, cnn_keys, mlp_keys):
    gamma = float(cfg.algo.gamma)
    tau = float(cfg.algo.tau)
    enc_tau = float(cfg.algo.encoder.tau)
    target_freq = int(cfg.algo.critic.per_rank_target_network_update_freq)
    actor_freq = int(cfg.algo.actor.per_rank_update_freq)
    decoder_freq = int(cfg.algo.decoder.per_rank_update_freq)
    l2_lambda = float(cfg.algo.decoder.l2_lambda)

    def normalize(batch, prefix=""):
        obs = {}
        for k in cnn_keys:
            obs[k] = batch[prefix + k].astype(jnp.float32) / 255.0
        for k in mlp_keys:
            obs[k] = batch[prefix + k].astype(jnp.float32)
        return obs

    def one_step(carry, inp):
        params, opt_states = carry
        batch, key = inp
        obs = normalize(batch)
        next_obs = normalize(batch, prefix="next_")

        # --- critic (encoder + Q heads together) --------------------------
        # actor's next actions come from ONLINE encoder features; target Q
        # consumes TARGET-encoder features (reference get_next_target_q_values)
        key, k_next = jax.random.split(key)
        online_next_feat = encoder.apply({"params": params["encoder"]}, next_obs)
        m, ls = actor.apply({"params": params["actor"]}, online_next_feat)
        next_actions, next_logp = sample_actions_features(actor, m, ls, k_next)
        target_next_feat = encoder.apply({"params": params["target_encoder"]}, next_obs)
        tq = qs.apply({"params": params["target_qs"]}, target_next_feat, next_actions)
        min_t = jnp.min(tq, axis=0) - jnp.exp(params["log_alpha"]) * next_logp
        y = batch["rewards"] + (1.0 - batch["terminated"]) * gamma * min_t

        def qf_loss_fn(enc_p, qs_p):
            feat = encoder.apply({"params": enc_p}, obs)
            q = qs.apply({"params": qs_p}, feat, batch["actions"])
            return critic_loss(q, jax.lax.stop_gradient(y), q.shape[0])

        qf_loss, (g_enc, g_qs) = jax.value_and_grad(qf_loss_fn, argnums=(0, 1))(
            params["encoder"], params["qs"]
        )
        updates, opt_states["qf"] = txs["qf"].update(
            {"encoder": g_enc, "qs": g_qs},
            opt_states["qf"],
            {"encoder": params["encoder"], "qs": params["qs"]},
        )
        new = optax.apply_updates({"encoder": params["encoder"], "qs": params["qs"]}, updates)
        params["encoder"], params["qs"] = new["encoder"], new["qs"]

        step = opt_states["step"] + 1

        # --- EMA targets --------------------------------------------------
        do_t = (step % target_freq) == 0
        params["target_qs"] = jax.tree.map(
            lambda t, s: jnp.where(do_t, (1 - tau) * t + tau * s, t), params["target_qs"], params["qs"]
        )
        params["target_encoder"] = jax.tree.map(
            lambda t, s: jnp.where(do_t, (1 - enc_tau) * t + enc_tau * s, t),
            params["target_encoder"],
            params["encoder"],
        )

        # --- actor + alpha (masked by update freq) ------------------------
        do_a = (step % actor_freq) == 0

        def actor_loss_fn(ap):
            feat = encoder.apply({"params": params["encoder"]}, obs, detach_conv=True)
            feat = jax.lax.stop_gradient(feat)
            m2, ls2 = actor.apply({"params": ap}, feat)
            acts, logp = sample_actions_features(actor, m2, ls2, jax.random.fold_in(key, 1))
            q = qs.apply({"params": params["qs"]}, feat, acts)
            return policy_loss(jnp.exp(params["log_alpha"]), logp, jnp.min(q, axis=0)), logp

        (a_loss, logp), a_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(params["actor"])
        a_grads = jax.tree.map(lambda g: jnp.where(do_a, g, 0.0), a_grads)
        updates, opt_states["actor"] = txs["actor"].update(a_grads, opt_states["actor"], params["actor"])
        updates = jax.tree.map(lambda u: jnp.where(do_a, u, 0.0), updates)
        params["actor"] = optax.apply_updates(params["actor"], updates)

        al_loss, al_grad = jax.value_and_grad(
            lambda la: entropy_loss(la, jax.lax.stop_gradient(logp), target_entropy)
        )(params["log_alpha"])
        al_grad = jnp.where(do_a, al_grad, 0.0)
        updates, opt_states["alpha"] = txs["alpha"].update(al_grad, opt_states["alpha"], params["log_alpha"])
        params["log_alpha"] = optax.apply_updates(params["log_alpha"], jnp.where(do_a, updates, 0.0))

        # --- decoder + encoder reconstruction -----------------------------
        do_d = (step % decoder_freq) == 0

        def recon_loss_fn(enc_p, dec_p):
            hidden = encoder.apply({"params": enc_p}, obs)
            rec = decoder.apply({"params": dec_p}, hidden)
            loss = 0.0
            for i, k in enumerate(cnn_keys):
                # distinct derived key per obs key: fold_in(key, 2) for all of
                # them would quantization-dither every camera with the SAME
                # noise pattern (and trip the rng-reuse lint's loop check)
                target = preprocess_obs(batch[k], bits=5, key=jax.random.fold_in(key, 2 + i))
                loss += jnp.mean(jnp.square(target - rec[k]))
                loss += l2_lambda * jnp.mean(0.5 * jnp.sum(jnp.square(hidden), axis=-1))
            for k in mlp_keys:
                loss += jnp.mean(jnp.square(batch[k] - rec[k]))
                loss += l2_lambda * jnp.mean(0.5 * jnp.sum(jnp.square(hidden), axis=-1))
            return loss

        rec_loss, (g_enc2, g_dec) = jax.value_and_grad(recon_loss_fn, argnums=(0, 1))(
            params["encoder"], params["decoder"]
        )
        g_enc2 = jax.tree.map(lambda g: jnp.where(do_d, g, 0.0), g_enc2)
        g_dec = jax.tree.map(lambda g: jnp.where(do_d, g, 0.0), g_dec)
        updates, opt_states["encoder"] = txs["encoder"].update(g_enc2, opt_states["encoder"], params["encoder"])
        params["encoder"] = optax.apply_updates(
            params["encoder"], jax.tree.map(lambda u: jnp.where(do_d, u, 0.0), updates)
        )
        updates, opt_states["decoder"] = txs["decoder"].update(g_dec, opt_states["decoder"], params["decoder"])
        params["decoder"] = optax.apply_updates(
            params["decoder"], jax.tree.map(lambda u: jnp.where(do_d, u, 0.0), updates)
        )

        opt_states["step"] = step
        metrics = {
            "Loss/value_loss": qf_loss,
            "Loss/policy_loss": a_loss,
            "Loss/alpha_loss": al_loss,
            "Loss/reconstruction_loss": rec_loss,
        }
        return (params, opt_states), metrics

    @partial(jax.jit, donate_argnums=(0, 1))
    def train(params, opt_states, batches, keys):
        (params, opt_states), metrics = jax.lax.scan(one_step, (params, opt_states), (batches, keys))
        return params, opt_states, jax.tree.map(jnp.mean, metrics)

    return train


@register_algorithm(name="sac_ae")
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
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys

    state = None
    if cfg.checkpoint.resume_from:
        state = CheckpointManager.load(cfg.checkpoint.resume_from)
    root_key, init_key = jax.random.split(state["rng"] if state else root_key)
    encoder, decoder, qs, actor, params = build_agent(
        dist, cfg, obs_space, action_space, init_key, state["params"] if state else None
    )
    act_dim = int(np.prod(action_space.shape))
    target_entropy = -act_dim

    txs = {
        "actor": instantiate(cfg.algo.actor.optimizer),
        "qf": instantiate(cfg.algo.critic.optimizer),
        "alpha": instantiate(cfg.algo.alpha.optimizer),
        "encoder": instantiate(cfg.algo.encoder.optimizer),
        "decoder": instantiate(cfg.algo.decoder.optimizer),
    }
    if state:
        opt_states = state["opt_states"]
    else:
        opt_states = {
            "actor": txs["actor"].init(params["actor"]),
            "qf": txs["qf"].init({"encoder": params["encoder"], "qs": params["qs"]}),
            "alpha": txs["alpha"].init(params["log_alpha"]),
            "encoder": txs["encoder"].init(params["encoder"]),
            "decoder": txs["decoder"].init(params["decoder"]),
            "step": jnp.zeros((), jnp.int32),
        }
    opt_states = dist.replicate(opt_states)  # all train state on the mesh before the first step

    buffer_size = int(cfg.buffer.size) if not cfg.dry_run else max(2 * num_envs, 8)
    rb = ReplayBuffer(
        buffer_size,
        num_envs,
        obs_keys=obs_keys,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}") if cfg.buffer.memmap else None,
        seed=cfg.seed + 1024 * rank,
    )
    if state and cfg.buffer.checkpoint and "rb" in state:
        rb.load_state_dict(state["rb"])

    train = make_train_fn(
        encoder, decoder, qs, actor, txs, cfg, target_entropy, cnn_keys, mlp_keys
    )

    @jax.jit
    def act(p, obs, key):
        feat = encoder.apply({"params": p["encoder"]}, obs)
        m, ls = actor.apply({"params": p["actor"]}, feat)
        actions, _ = sample_actions_features(actor, m, ls, key)
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

    # [G, B, ...] pixel batches: HBM ring on a single accelerator
    # (next_* frames are stored explicitly, hence the ×2 obs hint and the
    # next_-prefixed cnn keys keeping uint8), else host sampling
    prefetch = make_uniform_prefetcher(
        cfg,
        dist,
        rb,
        batch_size,
        cnn_keys=cnn_keys + tuple(f"next_{k}" for k in cnn_keys),
        row_bytes_hint=2 * estimate_row_bytes(obs_space, act_dim),
        emit=telem.emit,
    )

    # per-step inference on the player device (host CPU when the mesh is a
    # an accelerator); mirror re-syncs encoder+actor after a train burst
    mirror, pdev, player_key, root_key = make_param_mirror(
        cfg, dist.local_device, {"encoder": params["encoder"], "actor": params["actor"]}, root_key
    )
    telem.emit(mirror.placement)

    obs, _ = envs.reset(seed=cfg.seed)

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
                device_obs = prepare_obs_np(obs, cnn_keys, mlp_keys, num_envs, normalize=True)
                env_actions = np.asarray(act(mirror.current(), device_obs, k)).reshape(num_envs, act_dim)
            next_obs, rewards, terminated, truncated, info = envs.step(env_actions)
            policy_step += num_envs

            step_data: Dict[str, np.ndarray] = {}
            for k in cnn_keys:
                step_data[k] = np.asarray(obs[k]).reshape(1, num_envs, *obs_space[k].shape)
                step_data[f"next_{k}"] = np.asarray(next_obs[k]).reshape(1, num_envs, *obs_space[k].shape)
            for k in mlp_keys:
                step_data[k] = np.asarray(obs[k], np.float32).reshape(1, num_envs, -1)
                step_data[f"next_{k}"] = np.asarray(next_obs[k], np.float32).reshape(1, num_envs, -1)
            if "final_obs" in info:
                for i, fo in enumerate(info["final_obs"]):
                    if fo is not None:
                        for k in cnn_keys:
                            step_data[f"next_{k}"][0, i] = np.asarray(fo[k])
                        for k in mlp_keys:
                            step_data[f"next_{k}"][0, i] = np.asarray(fo[k], np.float32).reshape(-1)
            step_data["actions"] = env_actions.reshape(1, num_envs, act_dim).astype(np.float32)
            step_data["rewards"] = np.asarray(rewards, np.float32).reshape(1, num_envs, 1)
            step_data["terminated"] = np.asarray(terminated, np.float32).reshape(1, num_envs, 1)
            step_data["dones"] = (
                np.logical_or(terminated, truncated).astype(np.float32).reshape(1, num_envs, 1)
            )
            rb.add(step_data, validate_args=cfg.buffer.validate_args)
            obs = next_obs

            for ep_rew, ep_len in episode_stats(info):
                aggregator.update("Rewards/rew_avg", ep_rew)
                aggregator.update("Game/ep_len_avg", ep_len)

        if policy_step >= learning_starts:
            g = ratio(policy_step / dist.world_size)
            telem.record_grad_steps(g)
            if g > 0:
                with telem.span("Time/train_time"):
                    batches = prefetch.take(g)
                    root_key, sub = jax.random.split(root_key)
                    keys = jax.random.split(sub, g)
                    params, opt_states, metrics = train(params, opt_states, batches, keys)
                    mirror.refresh({"encoder": params["encoder"], "actor": params["actor"]})
                if not MetricAggregator.disabled:
                    # device refs held until the log-cadence host sync;
                    # skip entirely when metrics are off (bench legs)
                    pending_metrics.append(metrics)
            if policy_step < total_steps:
                # overlap the next sample (and its transfer/gather) with the
                # train burst the device is computing right now
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
        test_env = vectorize(
            Config({**cfg.to_dict(), "env": {**cfg.env.to_dict(), "num_envs": 1}}), cfg.seed, rank, log_dir
        ).envs[0]
        test(encoder, actor, params, test_env, cfg, log_dir, logger)
    if rank == 0 and not cfg.model_manager.disabled:
        from ...utils.model_manager import register_model

        register_model(
            cfg,
            {"encoder": params["encoder"], "decoder": params["decoder"], "actor": params["actor"]},
            log_dir,
        )
    if logger is not None:
        logger.close()


@register_evaluation(algorithms="sac_ae")
def evaluate_sac_ae(dist: Distributed, cfg: Config, state: Dict[str, Any]) -> None:
    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, dist.process_index)
    env = vectorize(cfg, cfg.seed, 0, log_dir).envs[0]
    root_key = dist.seed_everything(cfg.seed)
    encoder, decoder, qs, actor, params = build_agent(
        dist, cfg, env.observation_space, env.action_space, root_key, state["params"]
    )
    test(encoder, actor, params, env, cfg, log_dir, logger)
