"""Plan2Explore-DV3, exploration phase (Template B).

Reference sheeprl/algos/p2e_dv3/p2e_dv3_exploration.py (1059 LoC). One jitted
gradient step covering (reference train() :44-520):

1. DreamerV3 world-model update with reward/continue heads on *detached*
   latents (reference :160-165);
2. ensemble learning: members predict the next stochastic state via MSE in
   symlog-free space (reference :205-230);
3. exploration behaviour driven by `actor_exploration` against a **dict of
   critics** (`cfg.algo.critics_exploration`) — each with its own reward
   stream (ensemble-disagreement intrinsic or extrinsic reward model), its
   own target network, Moments normalizer and loss weight; the actor
   objective sums the weight-normalized advantages (reference :262-311);
4. task behaviour: the plain DV3 actor/critic update for zero-shot control
   (reference :374-480).

Target networks (task + every exploration critic) get the DV3 EMA update
every `per_rank_target_network_update_freq` steps (reference :915-929).
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
from ...data.device_ring import estimate_row_bytes, make_sequential_prefetcher
from ...distributions import (
    BernoulliSafeMode,
    Independent,
    MSEDistribution,
    OneHotCategoricalStraightThrough,
    TwoHotEncodingDistribution,
)
from ...ops import lambda_values as lambda_values_op
from ...ops.transforms import unrolled_cumprod
from ...optim import clipped
from ...parallel import Distributed
from ...parallel.mesh import maybe_shard_opt_state
from ...parallel.placement import make_param_mirror, player_device
from ...telemetry import Telemetry
from ...utils.checkpoint import CheckpointManager
from ...utils.env import episode_stats, patch_restarted_envs, vectorize
from ...utils.logger import get_log_dir, get_logger
from ...utils.metric import MetricAggregator
from ...utils.registry import register_algorithm, register_evaluation
from ...resilience import RunGuard
from ...utils.utils import Ratio, acknowledge_partial_donation, save_configs
from ..dreamer_v3.agent import WorldModel, actor_dists, sample_actor_actions
from ..dreamer_v3.dreamer_v3 import make_player
from ..dreamer_v3.loss import reconstruction_loss
from ..dreamer_v3.utils import (  # noqa: F401
    decode_obs_dists,
    extract_masks,
    init_moments,
    make_ens_apply,
    make_precision_applies,
    normalize_obs,
    prepare_obs,
    test,
    update_moments,
    use_phase_obs_loss,
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
    "State/kl",
    "State/post_entropy",
    "State/prior_entropy",
}
MODELS_TO_REGISTER = {
    "world_model",
    "ensembles",
    "actor_task",
    "critic_task",
    "target_critic_task",
    "actor_exploration",
    "critics_exploration",
    "moments_task",
    "moments_exploration",
}


def make_train_fn(
    wm: WorldModel,
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
    tau = float(cfg.algo.critic.tau)
    target_freq = int(cfg.algo.critic.per_rank_target_network_update_freq)
    moments_cfg = cfg.algo.actor.moments
    intrinsic_mult = float(cfg.algo.intrinsic_reward_multiplier)
    critics_cfg = {
        k: {"weight": float(v.weight), "reward_type": str(v.reward_type)}
        for k, v in cfg.algo.critics_exploration.items()
    }
    weights_sum = sum(c["weight"] for c in critics_cfg.values())

    # mixed precision: shared cast boundary (dreamer_v3/utils.py)
    wm_apply, actor_apply, critic_apply, _cast, _cdt, _ = make_precision_applies(
        cfg, wm, actor, critic
    )
    # phase-space observation loss rides the einsum decoder (decode_phases)
    phase_obs_loss = use_phase_obs_loss(wm_cfg, cnn_keys)
    ens_apply_c = make_ens_apply(ens_apply, _cast, _cdt)

    def moments_step(moments, lv):
        return update_moments(
            moments,
            lv,
            float(moments_cfg.decay),
            float(moments_cfg.max),
            float(moments_cfg.percentile.low),
            float(moments_cfg.percentile.high),
        )

    def one_step(params, opt_states, moments, batch, key):
        T, B = batch["rewards"].shape[:2]
        k_dyn, k_img_expl, k_img_task = jax.random.split(key, 3)
        batch_obs = normalize_obs({k: batch[k] for k in cnn_keys + mlp_keys}, cnn_keys)
        is_first = batch["is_first"].at[0].set(1.0)
        batch_actions = jnp.concatenate(
            [jnp.zeros_like(batch["actions"][:1]), batch["actions"][:-1]], axis=0
        )

        # ---------------- 1. world model ----------------------------------
        def wm_loss_fn(wm_params):
            embedded = wm_apply(wm_params, WorldModel.embed, batch_obs)

            def dyn_step(carry, xs):
                h, z = carry
                a, e, first, k = xs
                h, z, post_logits, prior_logits = wm_apply(
                    wm_params, WorldModel.dynamic, z, h, a, e, first, k
                )
                return (h, z), (h, z, post_logits, prior_logits)

            keys = jax.random.split(k_dyn, T)
            _, (hs, zs, post_logits, prior_logits) = jax.lax.scan(
                dyn_step,
                (jnp.zeros((B, R)), jnp.zeros((B, stoch_flat))),
                (batch_actions, embedded, is_first, keys),
            )
            latents = jnp.concatenate([zs, hs], axis=-1)
            latents_sg = jax.lax.stop_gradient(latents)
            po, obs_targets = decode_obs_dists(
                wm_apply, wm_params, WorldModel, latents, batch_obs, cnn_keys, mlp_keys, phase_obs_loss
            )
            # reward/continue on detached latents (reference :160-165)
            pr = TwoHotEncodingDistribution(
                wm_apply(wm_params, WorldModel.reward, latents_sg), dims=1
            )
            pc = Independent(
                BernoulliSafeMode(logits=wm_apply(wm_params, WorldModel.cont, latents_sg)), 1
            )
            continues_targets = 1 - batch["terminated"]
            S, D = int(wm_cfg.stochastic_size), int(wm_cfg.discrete_size)
            rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = (
                reconstruction_loss(
                    po,
                    obs_targets,
                    pr,
                    batch["rewards"],
                    prior_logits.reshape(T, B, S, D),
                    post_logits.reshape(T, B, S, D),
                    float(wm_cfg.kl_dynamic),
                    float(wm_cfg.kl_representation),
                    float(wm_cfg.kl_free_nats),
                    float(wm_cfg.kl_regularizer),
                    pc,
                    continues_targets,
                    float(wm_cfg.continue_scale_factor),
                )
            )
            aux = {
                "zs": zs,
                "hs": hs,
                "post_logits": post_logits,
                "prior_logits": prior_logits,
                "Loss/world_model_loss": rec_loss,
                "Loss/observation_loss": observation_loss,
                "Loss/reward_loss": reward_loss,
                "Loss/state_loss": state_loss,
                "Loss/continue_loss": continue_loss,
                "State/kl": kl,
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
            out = ens_apply_c(ens_params, inp)[:, :-1]  # [n, T-1, B, Z]
            dist = MSEDistribution(out, dims=1)
            return -jnp.sum(jnp.mean(dist.log_prob(zs[None, 1:]), axis=(1, 2)))

        ens_loss, ens_grads = jax.value_and_grad(ens_loss_fn)(params["ensembles"])
        updates, opt_states["ensembles"] = txs["ensembles"].update(
            ens_grads, opt_states["ensembles"], params["ensembles"]
        )
        params["ensembles"] = optax.apply_updates(params["ensembles"], updates)

        imagined_prior0 = zs.reshape(T * B, stoch_flat)
        recurrent0 = hs.reshape(T * B, R)
        true_continue0 = (1 - batch["terminated"]).reshape(T * B, 1)

        def rollout(actor_params, key):
            """DV3-style imagination: trajectories/actions have H+1 rows."""
            state0 = jnp.concatenate([imagined_prior0, recurrent0], axis=-1)
            pre0 = actor_apply(actor_params, jax.lax.stop_gradient(state0))
            k0, key = jax.random.split(key)
            acts0, _ = sample_actor_actions(actor, pre0, k0)
            a0 = jnp.concatenate(acts0, axis=-1)

            def img_step(carry, k):
                z, h, a = carry
                k_img_s, k_a = jax.random.split(k)
                z, h = wm_apply(params["wm"], WorldModel.imagination, z, h, a, k_img_s)
                state = jnp.concatenate([z, h], axis=-1)
                pre = actor_apply(actor_params, jax.lax.stop_gradient(state))
                acts, _ = sample_actor_actions(actor, pre, k_a)
                a = jnp.concatenate(acts, axis=-1)
                return (z, h, a), (state, a)

            keys = jax.random.split(key, horizon)
            _, (states, actions) = jax.lax.scan(img_step, (imagined_prior0, recurrent0, a0), keys)
            trajectories = jnp.concatenate([state0[None], states], axis=0)
            imagined_actions = jnp.concatenate([a0[None], actions], axis=0)
            return trajectories, imagined_actions

        def intrinsic_reward(trajectories, imagined_actions):
            inp = jax.lax.stop_gradient(jnp.concatenate([trajectories, imagined_actions], -1))
            preds = ens_apply_c(params["ensembles"], inp)  # [n, H+1, TB, Z]
            return jnp.var(preds, axis=0).mean(-1, keepdims=True) * intrinsic_mult

        def continues_of(trajectories):
            continues = Independent(
                BernoulliSafeMode(logits=wm_apply(params["wm"], WorldModel.cont, trajectories)), 1
            ).mode
            return jnp.concatenate([true_continue0[None], continues[1:]], axis=0)

        def policy_objective(dists, imagined_actions, advantage):
            if is_continuous:
                return advantage
            logprobs = []
            start = 0
            for d, adim in zip(dists, actions_dim):
                act = jax.lax.stop_gradient(imagined_actions[..., start : start + adim])
                logprobs.append(d.log_prob(act)[..., None][:-1])
                start += adim
            return sum(logprobs) * jax.lax.stop_gradient(advantage)

        # ---------------- 3. exploration behaviour ------------------------
        def expl_actor_loss_fn(actor_params, moments_expl):
            trajectories, imagined_actions = rollout(actor_params, k_img_expl)
            continues = continues_of(trajectories)
            discount = jax.lax.stop_gradient(unrolled_cumprod(continues * gamma) / gamma)
            advantage = 0.0
            new_moments = {}
            lv_per_critic = {}
            for name, ccfg in critics_cfg.items():
                values = TwoHotEncodingDistribution(
                    critic_apply(params["critics_exploration"][name]["critic"], trajectories),
                    dims=1,
                ).mean
                if ccfg["reward_type"] == "intrinsic":
                    reward = intrinsic_reward(trajectories, imagined_actions)
                else:
                    reward = TwoHotEncodingDistribution(
                        wm_apply(params["wm"], WorldModel.reward, trajectories), dims=1
                    ).mean
                lv = lambda_values_op(reward[1:], values[1:], continues[1:] * gamma, lmbda)
                m, offset, invscale = moments_step(moments_expl[name], lv)
                new_moments[name] = jax.tree.map(jax.lax.stop_gradient, m)
                normed_lv = (lv - offset) / invscale
                normed_baseline = (values[:-1] - offset) / invscale
                advantage = advantage + (normed_lv - normed_baseline) * (
                    ccfg["weight"] / weights_sum
                )
                lv_per_critic[name] = jax.lax.stop_gradient(lv)
            pre_dist = actor_apply(actor_params, jax.lax.stop_gradient(trajectories))
            dists = actor_dists(actor, pre_dist)
            objective = policy_objective(dists, imagined_actions, advantage)
            entropy = ent_coef * sum(d.entropy() for d in dists)[..., None]
            loss = -jnp.mean(discount[:-1] * (objective + entropy[:-1]))
            aux = {
                "trajectories": jax.lax.stop_gradient(trajectories),
                "discount": discount,
                "lv": lv_per_critic,
                "moments": new_moments,
            }
            return loss, aux

        (policy_loss_expl, e_aux), a_grads = jax.value_and_grad(expl_actor_loss_fn, has_aux=True)(
            params["actor_exploration"], moments["exploration"]
        )
        updates, opt_states["actor_exploration"] = txs["actor_exploration"].update(
            a_grads, opt_states["actor_exploration"], params["actor_exploration"]
        )
        params["actor_exploration"] = optax.apply_updates(params["actor_exploration"], updates)
        moments["exploration"] = e_aux["moments"]

        expl_value_losses = {}
        for name in critics_cfg:
            traj_sg = e_aux["trajectories"]
            lv_sg = e_aux["lv"][name]
            discount = e_aux["discount"]

            def c_loss_fn(c_params, name=name):
                qv = TwoHotEncodingDistribution(
                    critic_apply(c_params, traj_sg[:-1]), dims=1
                )
                tv = TwoHotEncodingDistribution(
                    critic_apply(params["critics_exploration"][name]["target"], traj_sg[:-1]),
                    dims=1,
                ).mean
                loss = -qv.log_prob(lv_sg) - qv.log_prob(jax.lax.stop_gradient(tv))
                return jnp.mean(loss * discount[:-1, ..., 0])

            vloss, c_grads = jax.value_and_grad(c_loss_fn)(
                params["critics_exploration"][name]["critic"]
            )
            updates, opt_states["critics_exploration"][name] = txs["critics_exploration"].update(
                c_grads,
                opt_states["critics_exploration"][name],
                params["critics_exploration"][name]["critic"],
            )
            params["critics_exploration"][name]["critic"] = optax.apply_updates(
                params["critics_exploration"][name]["critic"], updates
            )
            expl_value_losses[name] = vloss

        # ---------------- 4. task behaviour -------------------------------
        def task_actor_loss_fn(actor_params, moments_task):
            trajectories, imagined_actions = rollout(actor_params, k_img_task)
            values = TwoHotEncodingDistribution(
                critic_apply(params["critic_task"], trajectories), dims=1
            ).mean
            rewards_img = TwoHotEncodingDistribution(
                wm_apply(params["wm"], WorldModel.reward, trajectories), dims=1
            ).mean
            continues = continues_of(trajectories)
            lv = lambda_values_op(rewards_img[1:], values[1:], continues[1:] * gamma, lmbda)
            discount = jax.lax.stop_gradient(unrolled_cumprod(continues * gamma) / gamma)
            m, offset, invscale = moments_step(moments_task, lv)
            normed_lv = (lv - offset) / invscale
            normed_baseline = (values[:-1] - offset) / invscale
            advantage = normed_lv - normed_baseline
            pre_dist = actor_apply(actor_params, jax.lax.stop_gradient(trajectories))
            dists = actor_dists(actor, pre_dist)
            objective = policy_objective(dists, imagined_actions, advantage)
            entropy = ent_coef * sum(d.entropy() for d in dists)[..., None]
            loss = -jnp.mean(discount[:-1] * (objective + entropy[:-1]))
            aux = {
                "trajectories": jax.lax.stop_gradient(trajectories),
                "lambda_values": jax.lax.stop_gradient(lv),
                "discount": discount,
                "moments": jax.tree.map(jax.lax.stop_gradient, m),
            }
            return loss, aux

        (policy_loss_task, t_aux), a_grads = jax.value_and_grad(task_actor_loss_fn, has_aux=True)(
            params["actor_task"], moments["task"]
        )
        updates, opt_states["actor_task"] = txs["actor_task"].update(
            a_grads, opt_states["actor_task"], params["actor_task"]
        )
        params["actor_task"] = optax.apply_updates(params["actor_task"], updates)
        moments["task"] = t_aux["moments"]

        def task_critic_loss_fn(c_params):
            qv = TwoHotEncodingDistribution(
                critic_apply(c_params, t_aux["trajectories"][:-1]), dims=1
            )
            tv = TwoHotEncodingDistribution(
                critic_apply(params["target_critic_task"], t_aux["trajectories"][:-1]),
                dims=1,
            ).mean
            loss = -qv.log_prob(t_aux["lambda_values"]) - qv.log_prob(jax.lax.stop_gradient(tv))
            return jnp.mean(loss * t_aux["discount"][:-1, ..., 0])

        value_loss_task, c_grads = jax.value_and_grad(task_critic_loss_fn)(params["critic_task"])
        updates, opt_states["critic_task"] = txs["critic_task"].update(
            c_grads, opt_states["critic_task"], params["critic_task"]
        )
        params["critic_task"] = optax.apply_updates(params["critic_task"], updates)

        # ---------------- target EMAs -------------------------------------
        step = opt_states["step"] + 1
        do_t = (step % target_freq) == 0

        def ema(t, s):
            return jnp.where(do_t, (1 - tau) * t + tau * s, t)

        params["target_critic_task"] = jax.tree.map(
            ema, params["target_critic_task"], params["critic_task"]
        )
        for name in critics_cfg:
            params["critics_exploration"][name]["target"] = jax.tree.map(
                ema,
                params["critics_exploration"][name]["target"],
                params["critics_exploration"][name]["critic"],
            )
        opt_states["step"] = step

        S, D = int(wm_cfg.stochastic_size), int(wm_cfg.discrete_size)
        post_ent = Independent(
            OneHotCategoricalStraightThrough(logits=wm_aux["post_logits"].reshape(T, B, S, D)), 1
        ).entropy()
        prior_ent = Independent(
            OneHotCategoricalStraightThrough(logits=wm_aux["prior_logits"].reshape(T, B, S, D)), 1
        ).entropy()
        metrics = {
            "Loss/world_model_loss": wm_aux["Loss/world_model_loss"],
            "Loss/observation_loss": wm_aux["Loss/observation_loss"],
            "Loss/reward_loss": wm_aux["Loss/reward_loss"],
            "Loss/state_loss": wm_aux["Loss/state_loss"],
            "Loss/continue_loss": wm_aux["Loss/continue_loss"],
            "Loss/ensemble_loss": ens_loss,
            "State/kl": wm_aux["State/kl"],
            "State/post_entropy": jnp.mean(post_ent),
            "State/prior_entropy": jnp.mean(prior_ent),
            "Loss/policy_loss_exploration": policy_loss_expl,
            "Loss/policy_loss_task": policy_loss_task,
            "Loss/value_loss_task": value_loss_task,
        }
        for name, v in expl_value_losses.items():
            metrics[f"Loss/value_loss_exploration_{name}"] = v
        return params, opt_states, moments, metrics

    acknowledge_partial_donation()  # uint8/flag leaves can't alias; expected

    @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def train(params, opt_states, moments, batches, keys):
        """G gradient steps in one device call: scan `one_step` over
        `batches` [G, T, B, ...] / `keys` [G]; metrics come back [G]-shaped
        (see dreamer_v3.make_train_fn for the rationale — incl. why
        `batches` is donated: the biggest transient HBM buffer, consumed
        once; callers must pass fresh arrays every burst)."""

        def body(carry, xs):
            params, opt_states, moments = carry
            batch, key = xs
            params, opt_states, moments, metrics = one_step(
                params, opt_states, moments, batch, key
            )
            return (params, opt_states, moments), metrics

        (params, opt_states, moments), metrics = jax.lax.scan(
            body, (params, opt_states, moments), (batches, keys)
        )
        return params, opt_states, moments, metrics

    return train


def _player_params(params, actor_type: str):
    return {"wm": params["wm"], "actor": params[f"actor_{actor_type}"]}


@register_algorithm(name="p2e_dv3_exploration")
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
    wm, actor, critic, ens_apply, params = build_agent(
        dist, cfg, obs_space, actions_dim, is_continuous, init_key, state["params"] if state else None
    )
    critic_names = list(cfg.algo.critics_exploration.keys())

    txs = {
        "wm": clipped(instantiate(cfg.algo.world_model.optimizer), cfg.algo.world_model.clip_gradients),
        "ensembles": clipped(instantiate(cfg.algo.ensembles.optimizer), cfg.algo.ensembles.clip_gradients),
        "actor_task": clipped(instantiate(cfg.algo.actor.optimizer), cfg.algo.actor.clip_gradients),
        "critic_task": clipped(instantiate(cfg.algo.critic.optimizer), cfg.algo.critic.clip_gradients),
        "actor_exploration": clipped(instantiate(cfg.algo.actor.optimizer), cfg.algo.actor.clip_gradients),
        "critics_exploration": clipped(
            instantiate(cfg.algo.critic.optimizer), cfg.algo.critic.clip_gradients
        ),
    }
    if state:
        opt_states = state["opt_states"]
        moments = state["moments"]
    else:
        opt_states = {
            "wm": txs["wm"].init(params["wm"]),
            "ensembles": txs["ensembles"].init(params["ensembles"]),
            "actor_task": txs["actor_task"].init(params["actor_task"]),
            "critic_task": txs["critic_task"].init(params["critic_task"]),
            "actor_exploration": txs["actor_exploration"].init(params["actor_exploration"]),
            "critics_exploration": {
                k: txs["critics_exploration"].init(params["critics_exploration"][k]["critic"])
                for k in critic_names
            },
            "step": jnp.zeros((), jnp.int32),
        }
        moments = {"task": init_moments(), "exploration": {k: init_moments() for k in critic_names}}
    opt_states = maybe_shard_opt_state(cfg, dist, opt_states)
    moments = dist.replicate(moments)  # all train state on the mesh before the first step

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

    train = make_train_fn(wm, actor, critic, ens_apply, txs, cfg, is_continuous, actions_dim)
    actor_type = str(cfg.algo.player.actor_type)
    player_init, player_step_fn = make_player(wm, actor, cfg, actions_dim, is_continuous, num_envs)
    # Actor/learner split (parallel/placement.py): see dreamer_v3.py
    mirror, pdev, player_key, root_key = make_param_mirror(
        cfg, dist.local_device, _player_params(params, actor_type), root_key
    )

    # per-critic exploration metrics are config-driven (one entry per critic)
    aggregator_keys = AGGREGATOR_KEYS | {
        f"Loss/value_loss_exploration_{k}" for k in critic_names
    }
    telem = Telemetry.setup(cfg, log_dir, rank, logger=logger, aggregator_keys=aggregator_keys)
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

    prefetch = make_sequential_prefetcher(
        cfg,
        dist,
        rb,
        batch_size,
        seq_len,
        cnn_keys=cnn_keys,
        row_bytes_hint=estimate_row_bytes(obs_space, sum(actions_dim)),
        emit=telem.emit,
    )
    pending_metrics: list = []

    obs, _ = envs.reset(seed=cfg.seed)
    player_state = player_init(mirror.params)

    step_data: Dict[str, np.ndarray] = {}
    for k in obs_keys:
        step_data[k] = np.asarray(obs[k])[np.newaxis]
    step_data["actions"] = np.zeros((1, num_envs, act_total), np.float32)
    step_data["rewards"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["terminated"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["truncated"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["is_first"] = np.ones((1, num_envs, 1), np.float32)

    def _ckpt_state():
        s = {
            "params": params,
            "opt_states": opt_states,
            "moments": moments,
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
                env_actions, actions_cat, player_state, player_key = player_step_fn(
                    mirror.current(), host_obs, player_state, player_key,
                    action_mask=extract_masks(obs, num_envs),
                )
                actions_np = np.asarray(actions_cat)
                actions_env = np.asarray(env_actions)
                if is_continuous:
                    actions_env = actions_env.reshape(num_envs, -1)
                elif not is_multidiscrete:
                    actions_env = actions_env.reshape(num_envs)

            step_data["actions"] = actions_np.reshape(1, num_envs, -1)
            rb.add(step_data, validate_args=cfg.buffer.validate_args)

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
                step_data[k] = np.asarray(next_obs[k])[np.newaxis]
            step_data["is_first"] = np.zeros((1, num_envs, 1), np.float32)
            step_data["terminated"] = np.asarray(terminated, np.float32).reshape(1, num_envs, 1)
            step_data["truncated"] = np.asarray(truncated, np.float32).reshape(1, num_envs, 1)
            step_data["rewards"] = clip_rewards_fn(
                np.asarray(rewards, np.float32).reshape(1, num_envs, 1)
            )

            # in-flight env restart → truncation boundary + fresh recurrent
            # state (reference dreamer_v3.py:595-608 / patch_restarted_envs)
            restarted = patch_restarted_envs(info, dones, rb, step_data)
            if restarted is not None:
                player_state = player_init(mirror.current(), restarted, player_state)

            dones_idxes = np.nonzero(dones)[0].tolist()
            if dones_idxes:
                reset_data: Dict[str, np.ndarray] = {}
                for k in obs_keys:
                    reset_data[k] = real_next_obs[k][dones_idxes][np.newaxis]
                reset_data["terminated"] = step_data["terminated"][:, dones_idxes]
                reset_data["truncated"] = step_data["truncated"][:, dones_idxes]
                reset_data["actions"] = np.zeros((1, len(dones_idxes), act_total), np.float32)
                reset_data["rewards"] = step_data["rewards"][:, dones_idxes]
                reset_data["is_first"] = np.zeros_like(reset_data["terminated"])
                rb.add(reset_data, dones_idxes, validate_args=cfg.buffer.validate_args)
                step_data["rewards"][:, dones_idxes] = 0
                step_data["terminated"][:, dones_idxes] = 0
                step_data["truncated"][:, dones_idxes] = 0
                step_data["is_first"][:, dones_idxes] = 1
                mask = np.zeros((num_envs,), bool)
                mask[dones_idxes] = True
                player_state = player_init(mirror.current(), mask, player_state)

            obs = next_obs

        if policy_step >= learning_starts:
            per_rank_gradient_steps = ratio(policy_step / dist.world_size)
            telem.record_grad_steps(per_rank_gradient_steps)
            if per_rank_gradient_steps > 0:
                with telem.span("Time/train_time"):
                    batches = prefetch.take(per_rank_gradient_steps)  # [G, T, B, ...]
                    root_key, sub = jax.random.split(root_key)
                    params, opt_states, moments, metrics = train(
                        params,
                        opt_states,
                        moments,
                        batches,
                        jax.random.split(sub, per_rank_gradient_steps),
                    )
                if not MetricAggregator.disabled:
                    # device refs held until the log-cadence host sync;
                    # skip entirely when metrics are off (bench legs)
                    pending_metrics.append(metrics)
                mirror.refresh(_player_params(params, actor_type))
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
        # zero-shot test with the TASK actor (reference :1032-1035)
        test_cfg = Config({**cfg.to_dict(), "env": {**cfg.env.to_dict(), "num_envs": 1}})
        test_env = vectorize(test_cfg, cfg.seed, rank, log_dir).envs[0]
        t_init, t_step = make_player(wm, actor, cfg, actions_dim, is_continuous, 1)
        t_params = jax.device_put(_player_params(params, "task"), pdev)
        t_state = t_init(t_params)

        def _step(o, s, k, greedy, mask=None):
            env_actions, _, s, k = t_step(t_params, o, s, k, greedy, action_mask=mask)
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
                "critics_exploration": params["critics_exploration"],
            },
            log_dir,
        )
    if logger is not None:
        logger.close()


@register_evaluation(algorithms=["p2e_dv3_exploration", "p2e_dv3_finetuning"])
def evaluate_p2e_dv3(dist: Distributed, cfg: Config, state: Dict[str, Any]) -> None:
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
    # exploration ckpts: {wm, actor_task, ...}; finetuning ckpts: DV3 layout
    p = state["params"]
    from ..dreamer_v3.agent import build_agent as dv3_build_agent

    wm, actor, critic, params = dv3_build_agent(
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
    t_init, t_step = make_player(wm, actor, cfg, actions_dim, is_continuous, 1)
    pdev = player_device(cfg, dist.local_device)
    t_params = jax.device_put(params, pdev)
    t_state = t_init(t_params)

    def _step(o, s, k, greedy, mask=None):
        env_actions, _, s, k = t_step(t_params, o, s, k, greedy, action_mask=mask)
        return env_actions, s, k

    test(_step, t_state, env, cfg, log_dir, logger, device=pdev)
