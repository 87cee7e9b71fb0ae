"""DreamerV2 — discrete world-model RL (Template B).

Reference sheeprl/algos/dreamer_v2/dreamer_v2.py (792 LoC). TPU-native
re-design mirroring the DreamerV3 implementation in this repo:

* dynamic learning (reference python loop :146-160) → `lax.scan` of the
  fused RSSM cell; imagination (:258-276) → second scan;
* one jitted, donated-argument gradient step covering world model, actor
  (objective_mix reinforce/dynamics), critic and the hard target-critic
  copy (reference :695-701 copies every
  `critic.per_rank_target_network_update_freq` steps);
* Normal(·,1) observation/reward/value heads, KL balancing with free nats
  (loss.py), optional continue model (`use_continues`);
* `buffer.type ∈ {sequential, episode}` selects the replay backend
  (reference :496-517).
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
from ...data import (
    EnvIndependentReplayBuffer,
    EpisodeBuffer,
    SequentialReplayBuffer,
)
from ...data.device_ring import estimate_row_bytes, make_sequential_prefetcher
from ...distributions import Bernoulli, Independent, Normal
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
from .agent import (
    DV2Actor,
    DV2WorldModel,
    build_agent,
    dv2_actor_dists,
    dv2_exploration_noise,
    dv2_sample_actions,
)
from .loss import reconstruction_loss
from ..dreamer_v3.utils import make_precision_applies
from .utils import (
    AGGREGATOR_KEYS,
    compute_lambda_values,
    normalize_obs,
    prepare_obs,
    test,
)


def make_train_fn(
    wm: DV2WorldModel,
    actor: DV2Actor,
    critic,
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

    # mixed precision: shared cast boundary (dreamer_v3/utils.py)
    wm_apply, actor_apply, critic_apply, *_ = make_precision_applies(cfg, wm, actor, critic)

    def one_step(params, opt_states, batch, key):
        T, B = batch["rewards"].shape[:2]
        k_dyn, k_img = jax.random.split(key, 2)
        batch_obs = normalize_obs({k: batch[k] for k in cnn_keys + mlp_keys}, cnn_keys)
        is_first = batch["is_first"].at[0].set(1.0)

        # hard target-critic copy every `target_freq` steps, evaluated
        # *before* the gradient step (reference :695-701)
        step = opt_states["step"]
        do_t = (step % target_freq) == 0
        params["target_critic"] = jax.tree.map(
            lambda t, s: jnp.where(do_t, s, t), params["target_critic"], params["critic"]
        )

        # ---------------- world model ------------------------------------
        def wm_loss_fn(wm_params):
            embedded = wm_apply(wm_params, DV2WorldModel.embed, batch_obs)  # [T, B, E]

            def dyn_step(carry, xs):
                h, z = carry
                a, e, first, k = xs
                h, z, post_logits, prior_logits = wm_apply(
                    wm_params, DV2WorldModel.dynamic, z, h, a, e, first, k
                )
                return (h, z), (h, z, post_logits, prior_logits)

            keys = jax.random.split(k_dyn, T)
            h0 = jnp.zeros((B, R))
            z0 = jnp.zeros((B, stoch_flat))
            _, (hs, zs, post_logits, prior_logits) = jax.lax.scan(
                dyn_step, (h0, z0), (batch["actions"], embedded, is_first, keys)
            )
            latents = jnp.concatenate([zs, hs], axis=-1)
            recon = wm_apply(wm_params, DV2WorldModel.decode, latents)
            po = {
                k: Independent(Normal(recon[k], 1.0), 3 if k in cnn_keys else 1)
                for k in cnn_keys + mlp_keys
            }
            pr = Independent(Normal(wm_apply(wm_params, DV2WorldModel.reward, latents), 1.0), 1)
            if use_continues:
                pc = Independent(Bernoulli(logits=wm_apply(wm_params, DV2WorldModel.cont, latents)), 1)
                continues_targets = (1 - batch["terminated"]) * gamma
            else:
                pc = continues_targets = None
            S, D = int(wm_cfg.stochastic_size), int(wm_cfg.discrete_size)
            rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = reconstruction_loss(
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
                "State/kl": jnp.mean(kl),
            }
            return rec_loss, aux

        (wm_loss, wm_aux), wm_grads = jax.value_and_grad(wm_loss_fn, has_aux=True)(params["wm"])
        updates, opt_states["wm"] = txs["wm"].update(wm_grads, opt_states["wm"], params["wm"])
        params["wm"] = optax.apply_updates(params["wm"], updates)

        # ---------------- behaviour --------------------------------------
        imagined_prior0 = jax.lax.stop_gradient(wm_aux["zs"]).reshape(T * B, stoch_flat)
        recurrent0 = jax.lax.stop_gradient(wm_aux["hs"]).reshape(T * B, R)
        latent0 = jnp.concatenate([imagined_prior0, recurrent0], axis=-1)
        act_width = int(sum(actions_dim))

        def rollout(actor_params, key):
            """Imagination rollout (reference :258-276): trajectories[0] is the
            posterior latent, action[0] is zeros; H further imagined steps."""

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
            trajectories = jnp.concatenate([latent0[None], latents], axis=0)  # [H+1, TB, L]
            imagined_actions = jnp.concatenate(
                [jnp.zeros((1, T * B, act_width)), actions], axis=0
            )
            return trajectories, imagined_actions

        def actor_loss_fn(actor_params):
            trajectories, imagined_actions = rollout(actor_params, k_img)
            target_values = critic_apply(params["target_critic"], trajectories)
            rewards_img = wm_apply(params["wm"], DV2WorldModel.reward, trajectories)
            if use_continues:
                continues = nnprobs(wm_apply(params["wm"], DV2WorldModel.cont, trajectories))
                true_cont = (1 - batch["terminated"]).reshape(1, T * B, 1) * gamma
                continues = jnp.concatenate([true_cont, continues[1:]], axis=0)
            else:
                continues = jnp.ones_like(rewards_img) * gamma
            lv = compute_lambda_values(
                rewards_img[:-1], target_values[:-1], continues[:-1],
                bootstrap=target_values[-1], lmbda=lmbda,
            )
            discount = jax.lax.stop_gradient(
                unrolled_cumprod(jnp.concatenate([jnp.ones_like(continues[:1]), continues[:-1]], 0))
            )
            pre_dist = actor_apply(actor_params, jax.lax.stop_gradient(trajectories[:-2]))
            dists = dv2_actor_dists(actor, pre_dist)
            dynamics = lv[1:]
            advantage = jax.lax.stop_gradient(lv[1:] - target_values[:-2])
            logprobs = []
            start = 0
            for d, adim in zip(dists, actions_dim):
                act = jax.lax.stop_gradient(imagined_actions[1:-1, ..., start : start + adim])
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
            }
            return policy_loss, aux

        (policy_loss, a_aux), a_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(
            params["actor"]
        )
        updates, opt_states["actor"] = txs["actor"].update(a_grads, opt_states["actor"], params["actor"])
        params["actor"] = optax.apply_updates(params["actor"], updates)

        # ---------------- critic ------------------------------------------
        traj_sg = a_aux["trajectories"]
        lv_sg = a_aux["lambda_values"]
        discount = a_aux["discount"]

        def critic_loss_fn(critic_params):
            qv = Independent(Normal(critic_apply(critic_params, traj_sg[:-1]), 1.0), 1)
            return -jnp.mean(discount[:-1, ..., 0] * qv.log_prob(lv_sg))

        value_loss, c_grads = jax.value_and_grad(critic_loss_fn)(params["critic"])
        updates, opt_states["critic"] = txs["critic"].update(c_grads, opt_states["critic"], params["critic"])
        params["critic"] = optax.apply_updates(params["critic"], updates)
        opt_states["step"] = step + 1

        S, D = int(wm_cfg.stochastic_size), int(wm_cfg.discrete_size)
        from ...distributions import OneHotCategoricalStraightThrough

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
            "State/kl": wm_aux["State/kl"],
            "State/post_entropy": jnp.mean(post_ent),
            "State/prior_entropy": jnp.mean(prior_ent),
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
    wm,
    actor,
    cfg: Config,
    actions_dim,
    is_continuous: bool,
    num_envs: int,
    stoch_width: int = None,
):
    """Device-resident player (replaces reference PlayerDV2, agent.py:735-833):
    zero-initialised (h, z, a) carried on device between env steps.

    Shared with DreamerV1 (reference PlayerDV1, dreamer_v1/agent.py:219-298,
    identical apart from the stochastic-state width): pass `stoch_width` for
    non-discrete world models; world-model methods are resolved by name so any
    module exposing embed/recurrent_step/representation_step works."""
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    wm_cfg = cfg.algo.world_model
    stoch_flat = (
        stoch_width
        if stoch_width is not None
        else int(wm_cfg.stochastic_size) * int(wm_cfg.discrete_size)
    )
    R = int(wm_cfg.recurrent_model.recurrent_state_size)
    base_expl = float(cfg.algo.actor.expl_amount if cfg.select("algo.actor.expl_amount") else 0.0)
    expl_decay = float(cfg.algo.actor.expl_decay if cfg.select("algo.actor.expl_decay") else 0.0)
    expl_min = float(cfg.algo.actor.expl_min if cfg.select("algo.actor.expl_min") else 0.0)
    use_expl = base_expl > 0.0 or expl_min > 0.0

    def expl_amount_at(step_count: int) -> float:
        """Exploration schedule (reference Actor._get_expl_amount :499-503;
        the reference's `0.5 ** step / decay` has a precedence quirk — we use
        the intended half-life decay `0.5 ** (step / decay)`)."""
        amount = base_expl
        if expl_decay:
            amount *= 0.5 ** (float(step_count) / expl_decay)
        return max(amount, expl_min)

    @jax.jit
    def _masked_reset(mask, state):
        h, z, a = state
        m = mask[:, None]
        return (
            jnp.where(m, jnp.zeros_like(h), h),
            jnp.where(m, jnp.zeros_like(z), z),
            jnp.where(m, jnp.zeros_like(a), a),
        )

    def init_state(mask=None, state=None):
        """Fresh state: host numpy zeros (the caller commits them to the
        player device — no accelerator dispatch). Masked reset: jitted, runs
        wherever `state` is committed."""
        if state is None or mask is None:
            return (
                np.zeros((num_envs, R), np.float32),
                np.zeros((num_envs, stoch_flat), np.float32),
                np.zeros((num_envs, int(sum(actions_dim))), np.float32),
            )
        return _masked_reset(mask, state)

    @partial(jax.jit, static_argnames=("greedy",))
    def step(params, obs, state, key, greedy=False, expl_amount=0.0):
        h, z, a = state
        obs = normalize_obs(obs, cnn_keys)
        embedded = wm.apply({"params": params["wm"]}, obs, method="embed")
        h = wm.apply(
            {"params": params["wm"]},
            jnp.concatenate([z, a], -1),
            h,
            method="recurrent_step",
        )
        key, k1, k2, k3 = jax.random.split(key, 4)
        z = wm.apply({"params": params["wm"]}, h, embedded, k1, method="representation_step")
        pre = actor.apply({"params": params["actor"]}, jnp.concatenate([z, h], -1))
        acts, _ = dv2_sample_actions(actor, pre, k2, greedy=greedy)
        if not greedy and use_expl:
            acts = dv2_exploration_noise(actor, acts, expl_amount, k3)
        a = jnp.concatenate(acts, -1)
        if is_continuous:
            env_actions = a
        else:
            env_actions = jnp.stack([jnp.argmax(x, axis=-1) for x in acts], axis=-1)
        return env_actions, a, (h, z, a), key

    return init_state, step, expl_amount_at


def _build_buffer(cfg: Config, num_envs: int, obs_keys, log_dir: str, rank: int):
    """`buffer.type` selects sequential vs episode replay (reference :496-517)."""
    seq_len = int(cfg.algo.per_rank_sequence_length)
    buffer_size = int(cfg.buffer.size) if not cfg.dry_run else max(4 * seq_len, 64)
    buffer_type = str(cfg.buffer.type if cfg.select("buffer.type") else "sequential").lower()
    memmap_dir = (
        os.path.join(log_dir, "memmap_buffer", f"rank_{rank}") if cfg.buffer.memmap else None
    )
    if buffer_type == "sequential":
        return EnvIndependentReplayBuffer(
            buffer_size,
            n_envs=num_envs,
            obs_keys=obs_keys,
            memmap=cfg.buffer.memmap,
            memmap_dir=memmap_dir,
            buffer_cls=SequentialReplayBuffer,
            seed=cfg.seed + 1024 * rank,
        )
    if buffer_type == "episode":
        return EpisodeBuffer(
            buffer_size,
            minimum_episode_length=1 if cfg.dry_run else int(cfg.algo.per_rank_sequence_length),
            n_envs=num_envs,
            obs_keys=obs_keys,
            prioritize_ends=bool(cfg.buffer.prioritize_ends)
            if cfg.select("buffer.prioritize_ends")
            else False,
            memmap=cfg.buffer.memmap,
            memmap_dir=memmap_dir,
            seed=cfg.seed + 1024 * rank,
        )
    raise ValueError(
        f"Unrecognized buffer type: must be one of `sequential` or `episode`, received: {buffer_type}"
    )


@register_algorithm(name="dreamer_v2")
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
            "step": jnp.zeros((), jnp.int32),
        }
    opt_states = dist.replicate(opt_states)  # all train state on the mesh before the first step

    seq_len = int(cfg.algo.per_rank_sequence_length)
    rb = _build_buffer(cfg, num_envs, obs_keys, log_dir, rank)
    if state and cfg.buffer.checkpoint and "rb" in state:
        rb.load_state_dict(state["rb"])
    buffer_type = str(cfg.buffer.type if cfg.select("buffer.type") else "sequential").lower()

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

    # row 0: reset obs, zero action/reward, is_first=1 (reference :548-563)
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
                env_actions, actions_cat, player_state, player_key = player_step_fn(
                    mirror.current(), host_obs, player_state, player_key,
                    expl_amount=expl_amount_at(policy_step),
                )
                actions_np = np.asarray(actions_cat)
                actions_env = np.asarray(env_actions)
                if is_continuous:
                    actions_env = actions_env.reshape(num_envs, -1)
                elif not is_multidiscrete:
                    actions_env = actions_env.reshape(num_envs)

            # is_first of the *next* row = this step ended an episode
            # (reference :624 `is_first = terminated | truncated` of prev step)
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
                "target_critic": params["target_critic"],
            },
            log_dir,
        )
    if logger is not None:
        logger.close()


@register_evaluation(algorithms="dreamer_v2")
def evaluate_dreamer_v2(dist: Distributed, cfg: Config, state: Dict[str, Any]) -> None:
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
