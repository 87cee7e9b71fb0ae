"""Plan2Explore-DV2, exploration phase (Template B).

Reference sheeprl/algos/p2e_dv2/p2e_dv2_exploration.py (958 LoC). One jitted
gradient step:

1. DreamerV2 world-model update with reward/continue heads on *detached*
   latents;
2. ensemble learning: Gaussian NLL on the next discrete stochastic state
   (reference :195-220);
3. exploration behaviour — DV2 imagination driven by `actor_exploration`
   with ensemble-disagreement intrinsic reward, values from
   `target_critic_exploration` (reference :222-330);
4. task behaviour — the DV2 update with `actor_task`/`critic_task`/
   `target_critic_task` on the extrinsic reward model (reference :334-440).

Both target critics are hard-copied every
`critic.per_rank_target_network_update_freq` gradient steps.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Sequence

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from ...config import Config, instantiate
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
from ...utils.utils import Ratio, acknowledge_partial_donation, save_configs
from ..dreamer_v2.agent import DV2WorldModel, dv2_actor_dists, dv2_sample_actions
from ..dreamer_v2.dreamer_v2 import _build_buffer, make_player as make_dreamer_player
from ..dreamer_v2.loss import reconstruction_loss
from ..dreamer_v3.utils import make_ens_apply, make_precision_applies
from ..dreamer_v2.utils import (
    compute_lambda_values,
    normalize_obs,
    prepare_obs,
    test,
)
from .agent import build_agent

AGGREGATOR_KEYS = {
    "Rewards/rew_avg",
    "Game/ep_len_avg",
    "Loss/world_model_loss",
    "Loss/observation_loss",
    "Loss/reward_loss",
    "Loss/state_loss",
    "Loss/continue_loss",
    "Loss/ensemble_loss",
    "Loss/policy_loss_task",
    "Loss/value_loss_task",
    "Loss/policy_loss_exploration",
    "Loss/value_loss_exploration",
    "State/kl",
    "State/post_entropy",
    "State/prior_entropy",
    "Params/exploration_amount_task",
    "Params/exploration_amount_exploration",
    "Rewards/intrinsic",
    "Values_exploration/predicted_values",
    "Values_exploration/lambda_values",
}
MODELS_TO_REGISTER = {
    "world_model",
    "ensembles",
    "actor_task",
    "critic_task",
    "target_critic_task",
    "actor_exploration",
    "critic_exploration",
    "target_critic_exploration",
}


def make_train_fn(
    wm: DV2WorldModel,
    actor,
    critic,
    ens_apply,
    txs,
    cfg: Config,
    is_continuous: bool,
    actions_dim: Sequence[int],
):
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    wm_cfg = cfg.algo.world_model
    stoch_flat = int(wm_cfg.stochastic_size) * int(wm_cfg.discrete_size)
    R = int(wm_cfg.recurrent_model.recurrent_state_size)
    horizon = int(cfg.algo.horizon)
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    ent_coef = float(cfg.algo.actor.ent_coef)
    objective_mix = float(cfg.algo.actor.objective_mix)
    use_continues = bool(wm_cfg.use_continues)
    target_freq = int(cfg.algo.critic.per_rank_target_network_update_freq)
    intrinsic_mult = float(cfg.algo.intrinsic_reward_multiplier)
    act_width = int(sum(actions_dim))

    # mixed precision: shared cast boundary (dreamer_v3/utils.py)
    wm_apply, actor_apply, critic_apply, _cast, _cdt, _ = make_precision_applies(
        cfg, wm, actor, critic
    )
    ens_apply_c = make_ens_apply(ens_apply, _cast, _cdt)

    def one_step(params, opt_states, batch, key):
        T, B = batch["rewards"].shape[:2]
        k_dyn, k_img_expl, k_img_task = jax.random.split(key, 3)
        batch_obs = normalize_obs({k: batch[k] for k in cnn_keys + mlp_keys}, cnn_keys)
        is_first = batch["is_first"].at[0].set(1.0)

        # hard target copies before the gradient step (reference :695-701)
        step = opt_states["step"]
        do_t = (step % target_freq) == 0
        for name in ("task", "exploration"):
            params[f"target_critic_{name}"] = jax.tree.map(
                lambda t, s: jnp.where(do_t, s, t),
                params[f"target_critic_{name}"],
                params[f"critic_{name}"],
            )

        # ---------------- 1. world model ----------------------------------
        def wm_loss_fn(wm_params):
            embedded = wm_apply(wm_params, DV2WorldModel.embed, batch_obs)

            def dyn_step(carry, xs):
                h, z = carry
                a, e, first, k = xs
                h, z, post_logits, prior_logits = wm_apply(
                    wm_params, DV2WorldModel.dynamic, z, h, a, e, first, k
                )
                return (h, z), (h, z, post_logits, prior_logits)

            keys = jax.random.split(k_dyn, T)
            _, (hs, zs, post_logits, prior_logits) = jax.lax.scan(
                dyn_step,
                (jnp.zeros((B, R)), jnp.zeros((B, stoch_flat))),
                (batch["actions"], embedded, is_first, keys),
            )
            latents = jnp.concatenate([zs, hs], axis=-1)
            latents_sg = jax.lax.stop_gradient(latents)
            recon = wm_apply(wm_params, DV2WorldModel.decode, latents)
            po = {
                k: Independent(Normal(recon[k], 1.0), 3 if k in cnn_keys else 1)
                for k in cnn_keys + mlp_keys
            }
            pr = Independent(Normal(wm_apply(wm_params, DV2WorldModel.reward, latents_sg), 1.0), 1)
            if use_continues:
                pc = Independent(
                    Bernoulli(logits=wm_apply(wm_params, DV2WorldModel.cont, latents_sg)), 1
                )
                continues_targets = (1 - batch["terminated"]) * gamma
            else:
                pc = continues_targets = None
            S, D = int(wm_cfg.stochastic_size), int(wm_cfg.discrete_size)
            rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = (
                reconstruction_loss(
                    po,
                    batch_obs,
                    pr,
                    batch["rewards"],
                    prior_logits.reshape(T, B, S, D),
                    post_logits.reshape(T, B, S, D),
                    float(wm_cfg.kl_balancing_alpha),
                    float(wm_cfg.kl_free_nats),
                    bool(wm_cfg.kl_free_avg),
                    float(wm_cfg.kl_regularizer),
                    pc,
                    continues_targets,
                    float(wm_cfg.discount_scale_factor),
                )
            )
            from ...distributions import OneHotCategoricalStraightThrough

            post_ent = Independent(
                OneHotCategoricalStraightThrough(logits=post_logits.reshape(T, B, S, D)), 1
            ).entropy()
            prior_ent = Independent(
                OneHotCategoricalStraightThrough(logits=prior_logits.reshape(T, B, S, D)), 1
            ).entropy()
            aux = {
                "zs": zs,
                "hs": hs,
                "post_entropy": jnp.mean(post_ent),
                "prior_entropy": jnp.mean(prior_ent),
                "Loss/world_model_loss": rec_loss,
                "Loss/observation_loss": observation_loss,
                "Loss/reward_loss": reward_loss,
                "Loss/state_loss": state_loss,
                "Loss/continue_loss": continue_loss,
                "State/kl": jnp.mean(kl),
            }
            return rec_loss, aux

        (_, wm_aux), wm_grads = jax.value_and_grad(wm_loss_fn, has_aux=True)(params["wm"])
        updates, opt_states["wm"] = txs["wm"].update(wm_grads, opt_states["wm"], params["wm"])
        params["wm"] = optax.apply_updates(params["wm"], updates)

        zs = jax.lax.stop_gradient(wm_aux["zs"])
        hs = jax.lax.stop_gradient(wm_aux["hs"])

        # ---------------- 2. ensembles ------------------------------------
        def ens_loss_fn(ens_params):
            inp = jnp.concatenate([zs, hs, batch["actions"]], axis=-1)
            out = ens_apply_c(ens_params, inp)[:, :-1]
            dist = Independent(Normal(out, 1.0), 1)
            return -jnp.sum(jnp.mean(dist.log_prob(zs[None, 1:]), axis=(1, 2)))

        ens_loss, ens_grads = jax.value_and_grad(ens_loss_fn)(params["ensembles"])
        updates, opt_states["ensembles"] = txs["ensembles"].update(
            ens_grads, opt_states["ensembles"], params["ensembles"]
        )
        params["ensembles"] = optax.apply_updates(params["ensembles"], updates)

        imagined_prior0 = zs.reshape(T * B, stoch_flat)
        recurrent0 = hs.reshape(T * B, R)
        latent0 = jnp.concatenate([imagined_prior0, recurrent0], axis=-1)

        def rollout(actor_params, key):
            """DV2 imagination: trajectories[0] = posterior latent,
            actions[0] = zeros, H further steps (reference :222-249)."""

            def img_step(carry, k):
                z, h, latent = carry
                k_a, k_i = jax.random.split(k)
                pre = actor_apply(actor_params, jax.lax.stop_gradient(latent))
                acts, _ = dv2_sample_actions(actor, pre, k_a)
                a = jnp.concatenate(acts, axis=-1)
                z, h = wm_apply(params["wm"], DV2WorldModel.imagination, z, h, a, k_i)
                latent = jnp.concatenate([z, h], axis=-1)
                return (z, h, latent), (latent, a)

            keys = jax.random.split(key, horizon)
            _, (latents, actions) = jax.lax.scan(
                img_step, (imagined_prior0, recurrent0, latent0), keys
            )
            trajectories = jnp.concatenate([latent0[None], latents], axis=0)  # [H+1]
            imagined_actions = jnp.concatenate(
                [jnp.zeros((1, T * B, act_width)), actions], axis=0
            )
            return trajectories, imagined_actions

        def behaviour(actor_params, critic_params, target_params, reward_fn, key):
            """DV2 behaviour losses with pluggable reward + value targets."""

            def actor_loss_fn(a_params):
                trajectories, imagined_actions = rollout(a_params, key)
                target_values = critic_apply(target_params, trajectories)
                rewards_img = reward_fn(trajectories, imagined_actions)
                if use_continues:
                    continues = jax.nn.sigmoid(
                        wm_apply(params["wm"], DV2WorldModel.cont, trajectories)
                    )
                    true_cont = (1 - batch["terminated"]).reshape(1, T * B, 1) * gamma
                    continues = jnp.concatenate([true_cont, continues[1:]], axis=0)
                else:
                    continues = jnp.ones_like(rewards_img) * gamma
                lv = compute_lambda_values(
                    rewards_img[:-1], target_values[:-1], continues[:-1],
                    bootstrap=target_values[-1], lmbda=lmbda,
                )
                discount = jax.lax.stop_gradient(
                    unrolled_cumprod(
                        jnp.concatenate([jnp.ones_like(continues[:1]), continues[:-1]], 0)
                    )
                )
                pre_dist = actor_apply(a_params, jax.lax.stop_gradient(trajectories[:-2]))
                dists = dv2_actor_dists(actor, pre_dist)
                dynamics = lv[1:]
                advantage = jax.lax.stop_gradient(lv[1:] - target_values[:-2])
                logprobs = []
                start = 0
                for d, adim in zip(dists, actions_dim):
                    act = jax.lax.stop_gradient(
                        imagined_actions[1:-1, ..., start : start + adim]
                    )
                    logprobs.append(d.log_prob(act)[..., None])
                    start += adim
                reinforce = sum(logprobs) * advantage
                objective = objective_mix * reinforce + (1 - objective_mix) * dynamics
                try:
                    entropy = ent_coef * sum(d.entropy() for d in dists)[..., None]
                except NotImplementedError:
                    entropy = jnp.zeros_like(objective)
                policy_loss = -jnp.mean(discount[:-2] * (objective + entropy))
                aux = {
                    "trajectories": jax.lax.stop_gradient(trajectories),
                    "lambda_values": jax.lax.stop_gradient(lv),
                    "discount": discount,
                    "rewards": jax.lax.stop_gradient(rewards_img),
                    "values": jax.lax.stop_gradient(target_values),
                }
                return policy_loss, aux

            (policy_loss, aux), a_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(
                actor_params
            )

            def critic_loss_fn(c_params):
                qv = Independent(
                    Normal(critic_apply(c_params, aux["trajectories"][:-1]), 1.0), 1
                )
                return -jnp.mean(aux["discount"][:-1, ..., 0] * qv.log_prob(aux["lambda_values"]))

            value_loss, c_grads = jax.value_and_grad(critic_loss_fn)(critic_params)
            return policy_loss, a_grads, value_loss, c_grads, aux

        # ---------------- 3. exploration behaviour ------------------------
        def intrinsic_reward_fn(trajectories, imagined_actions):
            inp = jax.lax.stop_gradient(jnp.concatenate([trajectories, imagined_actions], -1))
            preds = ens_apply_c(params["ensembles"], inp)
            return jnp.var(preds, axis=0).mean(-1, keepdims=True) * intrinsic_mult

        policy_loss_expl, a_grads, value_loss_expl, c_grads, aux_expl = behaviour(
            params["actor_exploration"],
            params["critic_exploration"],
            params["target_critic_exploration"],
            intrinsic_reward_fn,
            k_img_expl,
        )
        updates, opt_states["actor_exploration"] = txs["actor_exploration"].update(
            a_grads, opt_states["actor_exploration"], params["actor_exploration"]
        )
        params["actor_exploration"] = optax.apply_updates(params["actor_exploration"], updates)
        updates, opt_states["critic_exploration"] = txs["critic_exploration"].update(
            c_grads, opt_states["critic_exploration"], params["critic_exploration"]
        )
        params["critic_exploration"] = optax.apply_updates(params["critic_exploration"], updates)

        # ---------------- 4. task behaviour -------------------------------
        def extrinsic_reward_fn(trajectories, imagined_actions):
            return wm_apply(params["wm"], DV2WorldModel.reward, trajectories)

        policy_loss_task, a_grads, value_loss_task, c_grads, _ = behaviour(
            params["actor_task"],
            params["critic_task"],
            params["target_critic_task"],
            extrinsic_reward_fn,
            k_img_task,
        )
        updates, opt_states["actor_task"] = txs["actor_task"].update(
            a_grads, opt_states["actor_task"], params["actor_task"]
        )
        params["actor_task"] = optax.apply_updates(params["actor_task"], updates)
        updates, opt_states["critic_task"] = txs["critic_task"].update(
            c_grads, opt_states["critic_task"], params["critic_task"]
        )
        params["critic_task"] = optax.apply_updates(params["critic_task"], updates)
        opt_states["step"] = step + 1

        metrics = {
            "Loss/world_model_loss": wm_aux["Loss/world_model_loss"],
            "Loss/observation_loss": wm_aux["Loss/observation_loss"],
            "Loss/reward_loss": wm_aux["Loss/reward_loss"],
            "Loss/state_loss": wm_aux["Loss/state_loss"],
            "Loss/continue_loss": wm_aux["Loss/continue_loss"],
            "Loss/ensemble_loss": ens_loss,
            "State/kl": wm_aux["State/kl"],
            "State/post_entropy": wm_aux["post_entropy"],
            "State/prior_entropy": wm_aux["prior_entropy"],
            "Loss/policy_loss_exploration": policy_loss_expl,
            "Loss/value_loss_exploration": value_loss_expl,
            "Loss/policy_loss_task": policy_loss_task,
            "Loss/value_loss_task": value_loss_task,
            "Rewards/intrinsic": jnp.mean(aux_expl["rewards"]),
            "Values_exploration/predicted_values": jnp.mean(aux_expl["values"]),
            "Values_exploration/lambda_values": jnp.mean(aux_expl["lambda_values"]),
        }
        return params, opt_states, metrics

    acknowledge_partial_donation()  # uint8/flag leaves can't alias; expected

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def train(params, opt_states, batches, keys):
        """G gradient steps in one device call: scan `one_step` over
        `batches` [G, T, B, ...] / `keys` [G]; metrics come back [G]-shaped
        (see dreamer_v3.make_train_fn for the rationale — incl. why
        `batches` is donated: the biggest transient HBM buffer, consumed
        once; callers must pass fresh arrays every burst)."""

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


def _player_params(params, actor_type: str):
    return {"wm": params["wm"], "actor": params[f"actor_{actor_type}"]}


@register_algorithm(name="p2e_dv2_exploration")
def main(dist: Distributed, cfg: Config) -> None:
    root_key = dist.seed_everything(cfg.seed)
    rank = dist.process_index
    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, rank)
    if rank == 0:
        save_configs(cfg, log_dir)

    # crash-prone suites restart in place. Only the sequential buffer can
    # re-establish the crash boundary (mark_restart); with an episode buffer
    # the wrapper's truncate-on-crash reporting closes the episode instead.
    _seq_buffer = str(cfg.select("buffer.type") or "sequential").lower() == "sequential"
    envs = vectorize(cfg, cfg.seed, rank, log_dir, restart_handled_by_loop=_seq_buffer)
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
    wm, actor, critic, ens_apply, params = build_agent(
        dist, cfg, obs_space, actions_dim, is_continuous, init_key, state["params"] if state else None
    )

    txs = {
        "wm": clipped(instantiate(cfg.algo.world_model.optimizer), cfg.algo.world_model.clip_gradients),
        "ensembles": clipped(instantiate(cfg.algo.ensembles.optimizer), cfg.algo.ensembles.clip_gradients),
        "actor_task": clipped(instantiate(cfg.algo.actor.optimizer), cfg.algo.actor.clip_gradients),
        "critic_task": clipped(instantiate(cfg.algo.critic.optimizer), cfg.algo.critic.clip_gradients),
        "actor_exploration": clipped(instantiate(cfg.algo.actor.optimizer), cfg.algo.actor.clip_gradients),
        "critic_exploration": clipped(instantiate(cfg.algo.critic.optimizer), cfg.algo.critic.clip_gradients),
    }
    if state:
        opt_states = state["opt_states"]
    else:
        opt_states = {k: txs[k].init(params[k]) for k in txs}
        opt_states["step"] = jnp.zeros((), jnp.int32)
    opt_states = dist.replicate(opt_states)  # all train state on the mesh before the first step

    rb = _build_buffer(cfg, num_envs, obs_keys, log_dir, rank)
    if state and cfg.buffer.checkpoint and "rb" in state:
        rb.load_state_dict(state["rb"])
    buffer_type = str(cfg.buffer.type if cfg.select("buffer.type") else "sequential").lower()
    seq_len = int(cfg.algo.per_rank_sequence_length)

    train = make_train_fn(wm, actor, critic, ens_apply, txs, cfg, is_continuous, actions_dim)
    actor_type = str(cfg.algo.player.actor_type)
    player_init, player_step_fn, expl_amount_at = make_dreamer_player(
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
        return _player_params(params, actor_type)

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
    step_data["is_first"] = np.ones((1, num_envs, 1), np.float32)
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
                aggregator.update(f"Params/exploration_amount_{actor_type}", expl_amount)
                env_actions, actions_cat, player_state, player_key = player_step_fn(
                    mirror.current(), host_obs, player_state, player_key,
                    expl_amount=expl_amount,
                )
                actions_np = np.asarray(actions_cat)
                actions_env = np.asarray(env_actions)
                if is_continuous:
                    actions_env = actions_env.reshape(num_envs, -1)
                elif not is_multidiscrete:
                    actions_env = actions_env.reshape(num_envs)

            prev_done = np.logical_or(step_data["terminated"], step_data["truncated"]).astype(
                np.float32
            )
            next_obs, rewards, terminated, truncated, info = envs.step(actions_env)
            policy_step += num_envs
            dones = np.logical_or(terminated, truncated)
            if cfg.dry_run and buffer_type == "episode":
                terminated = np.ones_like(terminated)
                truncated = np.ones_like(truncated)
                dones = np.ones_like(dones)

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
            step_data["is_first"] = prev_done
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
        t_init, t_step, _ = make_dreamer_player(wm, actor, cfg, actions_dim, is_continuous, 1)
        t_params = jax.device_put(_player_params(params, "task"), pdev)
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
                "ensembles": params["ensembles"],
                "actor_task": params["actor_task"],
                "critic_task": params["critic_task"],
                "target_critic_task": params["target_critic_task"],
                "actor_exploration": params["actor_exploration"],
                "critic_exploration": params["critic_exploration"],
                "target_critic_exploration": params["target_critic_exploration"],
            },
            log_dir,
        )
    if logger is not None:
        logger.close()


@register_evaluation(algorithms=["p2e_dv2_exploration", "p2e_dv2_finetuning"])
def evaluate_p2e_dv2(dist: Distributed, cfg: Config, state: Dict[str, Any]) -> None:
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
    p = state["params"]
    from ..dreamer_v2.agent import build_agent as dv2_build_agent

    wm, actor, critic, params = dv2_build_agent(
        dist,
        cfg,
        env.observation_space,
        actions_dim,
        is_continuous,
        root_key,
        {
            "wm": p["wm"],
            "actor": p["actor_task"] if "actor_task" in p else p["actor"],
            "critic": p["critic_task"] if "critic_task" in p else p["critic"],
            "target_critic": p["target_critic_task"]
            if "target_critic_task" in p
            else p["target_critic"],
        },
    )
    t_init, t_step, _ = make_dreamer_player(wm, actor, cfg, actions_dim, is_continuous, 1)
    pdev = player_device(cfg, dist.local_device)
    t_params = jax.device_put(params, pdev)
    t_state = jax.device_put(t_init(), pdev)

    def _step(o, s, k, greedy):
        env_actions, _, s, k = t_step(t_params, o, s, k, greedy)
        return env_actions, s, k

    test(_step, t_state, env, cfg, log_dir, logger, device=pdev)
