"""Recurrent PPO — on-policy training over LSTM sequences (Template A).

Reference sheeprl/algos/ppo_recurrent/ppo_recurrent.py (524 LoC). TPU-native
re-design:

* rollout on host with a single-step jitted act fn carrying the LSTM state
  on device; hidden states and previous actions are recorded per step;
* instead of splitting the rollout into variable-length episodes and
  pack-padding them (reference :407-445 — dynamic shapes), the [T, N]
  rollout is chunked into fixed-length sequences of
  `per_rank_sequence_length`, each seeded with its recorded (hx, cx) and
  reset inside the LSTM scan at episode boundaries via `is_first`. The same
  steps contribute to the same losses — only the truncation points of BPTT
  differ (fixed offsets vs episode starts), and no step is ever padding;
* the whole update (epochs × minibatches of sequences) is one jitted,
  donated-argument XLA program, exactly like this repo's PPO;
* truncation bootstrapping via the player value head on the final obs
  (reference :314-335).
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
from ...utils.env import episode_stats, vectorize
from ...utils.logger import get_log_dir, get_logger
from ...utils.registry import register_algorithm, register_evaluation
from ..ppo.loss import entropy_loss, policy_loss, value_loss
from .agent import RecurrentPPOAgent, actions_and_log_probs, backbone_name, build_agent
from .loop import LoopRun
from .utils import prepare_obs, test, update_coefs


def make_act_fn(module: RecurrentPPOAgent):
    @jax.jit
    def act(params, obs, prev_actions, carry, key):
        actor_out, value, carry = module.apply(
            {"params": params}, obs, prev_actions, jnp.zeros((1, prev_actions.shape[1], 1)), carry
        )
        actor_out = [a[0] for a in actor_out]  # drop L=1 axis
        actions, logprob, _ = actions_and_log_probs(actor_out, module.is_continuous, key=key)
        return actions, logprob, value[0], carry

    return act


def make_value_fn(module: RecurrentPPOAgent):
    @jax.jit
    def value_fn(params, obs, prev_actions, carry):
        _, value, _ = module.apply(
            {"params": params}, obs, prev_actions, jnp.zeros((1, prev_actions.shape[1], 1)), carry
        )
        return value[0]

    return value_fn


def make_update_fn(module: RecurrentPPOAgent, tx, cfg: Config, num_minibatches: int, mb_size: int):
    """Epochs × minibatches-of-sequences as one jitted program (the reference
    dispatches one torch step per minibatch, ppo_recurrent.py:57-117)."""
    update_epochs = int(cfg.algo.update_epochs)
    clip_vloss = bool(cfg.algo.clip_vloss)
    normalize_advantages = bool(cfg.algo.normalize_advantages)
    reduction = str(cfg.algo.loss_reduction)
    obs_keys = tuple(cfg.algo.cnn_keys.encoder) + tuple(cfg.algo.mlp_keys.encoder)

    def loss_fn(params, mb: Dict[str, jax.Array], coefs: Dict[str, jax.Array]):
        # minibatch arrives sequence-major [mb, L, ...] → time-major
        tm = lambda x: jnp.swapaxes(x, 0, 1)
        obs = {k: tm(mb[f"obs:{k}"]) for k in obs_keys}
        carry = (mb["cx0"], mb["hx0"])
        actor_out, new_values, _ = module.apply(
            {"params": params}, obs, tm(mb["prev_actions"]), tm(mb["is_first"]), carry
        )
        actions = tm(mb["actions"])
        if not module.is_continuous:
            actions = actions.astype(jnp.int32)
        _, new_logprobs, entropy = actions_and_log_probs(
            actor_out, module.is_continuous, actions=actions
        )
        advantages = tm(mb["advantages"])
        if normalize_advantages:
            advantages = (advantages - jnp.mean(advantages)) / (jnp.std(advantages) + 1e-8)
        pg_loss = policy_loss(
            new_logprobs, tm(mb["logprobs"]), advantages, coefs["clip_coef"], reduction
        )
        v_loss = value_loss(
            new_values, tm(mb["values"]), tm(mb["returns"]), coefs["clip_coef"], clip_vloss, reduction
        )
        ent_loss = entropy_loss(entropy, reduction)
        loss = pg_loss + coefs["vf_coef"] * v_loss + coefs["ent_coef"] * ent_loss
        return loss, {
            "Loss/policy_loss": pg_loss,
            "Loss/value_loss": v_loss,
            "Loss/entropy_loss": ent_loss,
        }

    @partial(jax.jit, donate_argnums=(0, 1))
    def update(params, opt_state, data: Dict[str, jax.Array], coefs, key):
        num_sequences = next(iter(data.values())).shape[0]

        def epoch_step(carry, _):
            params, opt_state, key = carry
            key, pk = jax.random.split(key)
            perm = jax.random.permutation(pk, num_sequences)
            idxs = perm[: num_minibatches * mb_size].reshape(num_minibatches, mb_size)

            def mb_step(carry2, idx):
                params, opt_state = carry2
                mb = jax.tree.map(lambda x: jnp.take(x, idx, axis=0), data)
                (_, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, mb, coefs)
                updates, new_opt_state = tx.update(grads, opt_state, params)
                updates = jax.tree.map(lambda u: u * coefs["lr_frac"], updates)
                params = optax.apply_updates(params, updates)
                return (params, new_opt_state), aux

            (params, opt_state), auxs = jax.lax.scan(mb_step, (params, opt_state), idxs)
            return (params, opt_state, key), auxs

        (params, opt_state, key), auxs = jax.lax.scan(
            epoch_step, (params, opt_state, key), None, length=update_epochs
        )
        metrics = jax.tree.map(jnp.mean, auxs)
        return params, opt_state, metrics

    return update


@register_algorithm(name="ppo_recurrent")
def main(dist: Distributed, cfg: Config) -> None:
    if backbone_name(cfg) != "lstm":  # the backbone is the recipe's choice: a sequence model has a loop of its own
        from . import sequence_policy

        return sequence_policy.main(dist, cfg)
    run = LoopRun(dist, cfg)  # the run around the loop, shared with the sequence backbone's
    rank, log_dir, logger, envs, state = run.rank, run.log_dir, run.logger, run.envs, run.state
    obs_space = envs.single_observation_space
    action_space = envs.single_action_space
    num_envs = int(cfg.env.num_envs)
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys
    if not isinstance(obs_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {obs_space}")

    root_key, init_key = jax.random.split(run.root_key)
    module, params = build_agent(
        dist, cfg, obs_space, action_space, init_key, state["params"] if state else None
    )
    actions_dim = module.actions_dim
    act_width = int(sum(actions_dim))
    H = int(cfg.algo.rnn.lstm.hidden_size)
    reset_on_done = bool(cfg.algo.reset_recurrent_state_on_done)

    tx = clipped(instantiate(cfg.algo.optimizer), cfg.algo.get("max_grad_norm", 0.0))
    opt_state = dist.replicate(state["opt_state"] if state else tx.init(params))

    rollout_steps = int(cfg.algo.rollout_steps)
    seq_len = int(cfg.algo.per_rank_sequence_length)
    if rollout_steps % seq_len != 0:
        raise ValueError(
            f"rollout_steps ({rollout_steps}) must be divisible by "
            f"per_rank_sequence_length ({seq_len}) for fixed-shape sequence chunking"
        )
    num_chunks = rollout_steps // seq_len
    num_sequences = num_chunks * num_envs
    num_batches = int(cfg.algo.per_rank_num_batches) * dist.world_size
    mb_size = max(num_sequences // num_batches, 1) if num_batches > 0 else 1
    num_minibatches = num_sequences // mb_size

    rb = ReplayBuffer(
        rollout_steps,
        num_envs,
        obs_keys=obs_keys,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}") if cfg.buffer.memmap else None,
        seed=cfg.seed + 1024 * rank,
    )

    act = make_act_fn(module)
    value_fn = make_value_fn(module)
    update = make_update_fn(module, tx, cfg, num_minibatches, mb_size)
    gae_fn = jax.jit(
        partial(gae_op, num_steps=rollout_steps, gamma=cfg.algo.gamma, gae_lambda=cfg.algo.gae_lambda)
    )

    run.begin(num_envs * rollout_steps)
    telem = run.telem
    aggregator = telem.aggregator

    def to_onehot(np_actions: np.ndarray) -> np.ndarray:
        """int actions [N, n_dims] → concatenated one-hot [N, act_width]."""
        if module.is_continuous:
            return np_actions.reshape(num_envs, -1).astype(np.float32)
        oh = []
        for i, d in enumerate(actions_dim):
            oh.append(np.eye(d, dtype=np.float32)[np_actions[:, i]])
        return np.concatenate(oh, axis=-1)

    # per-step inference on the player device (host CPU when the mesh is a
    # an accelerator); blocking refresh keeps PPO strictly on-policy
    mirror, pdev, player_key, root_key = make_param_mirror(
        cfg, dist.local_device, params, root_key, allow_async=False
    )
    telem.emit(mirror.placement)

    obs, _ = envs.reset(seed=cfg.seed)
    carry = jax.device_put(module.initial_states(num_envs), pdev)
    prev_actions = np.zeros((num_envs, act_width), np.float32)

    def learner():
        return params, opt_state, root_key

    for update_iter in range(run.start_iter, run.num_updates + 1):
        telem.tick(run.policy_step)
        chunk_cx: list = []
        chunk_hx: list = []
        with telem.span("Time/env_interaction_time"):
            for t in range(rollout_steps):
                device_obs = prepare_obs(obs, cnn_keys, mlp_keys, num_envs)
                player_key, act_key = jax.random.split(player_key)
                if t % seq_len == 0:
                    # only chunk-start states seed training sequences — no
                    # per-step device→host carry copies
                    chunk_cx.append(np.asarray(carry[0]))
                    chunk_hx.append(np.asarray(carry[1]))
                actions, logprobs, values, carry = act(
                    mirror.current(), device_obs, prev_actions[None], carry, act_key
                )
                np_actions = np.asarray(actions)
                if module.is_continuous:
                    env_actions = np_actions.reshape(num_envs, -1)
                elif isinstance(action_space, gym.spaces.MultiDiscrete):
                    env_actions = np_actions.reshape(num_envs, -1)
                else:
                    env_actions = np_actions.reshape(num_envs)
                next_obs, rewards, terminated, truncated, info = envs.step(env_actions)
                run.policy_step += num_envs

                rewards = np.asarray(rewards, dtype=np.float32).reshape(num_envs, 1)
                dones = np.logical_or(terminated, truncated).astype(np.float32).reshape(num_envs, 1)
                actions_oh = to_onehot(np_actions)

                # truncation bootstrapping (reference :314-335): value of the
                # final obs, evaluated with the post-step recurrent state
                if np.any(truncated) and "final_obs" in info:
                    final_obs = info["final_obs"]
                    trunc_idx = np.nonzero(truncated)[0]
                    stacked = {
                        k: np.stack([np.asarray(final_obs[i][k]) for i in trunc_idx])
                        for k in obs_keys
                    }
                    sub_carry = (
                        np.asarray(carry[0])[trunc_idx],
                        np.asarray(carry[1])[trunc_idx],
                    )
                    vals = np.asarray(
                        value_fn(
                            mirror.current(),
                            prepare_obs(stacked, cnn_keys, mlp_keys, len(trunc_idx)),
                            actions_oh[trunc_idx][None],
                            sub_carry,
                        )
                    )
                    rewards[trunc_idx] += cfg.algo.gamma * vals.reshape(-1, 1)

                step_data: Dict[str, np.ndarray] = {}
                for k in obs_keys:
                    step_data[f"obs:{k}"] = np.asarray(obs[k]).reshape(1, num_envs, *obs_space[k].shape)
                step_data["actions"] = np_actions.reshape(1, num_envs, -1).astype(np.float32)
                step_data["prev_actions"] = prev_actions.reshape(1, num_envs, act_width)
                step_data["logprobs"] = np.asarray(logprobs).reshape(1, num_envs, 1)
                step_data["values"] = np.asarray(values).reshape(1, num_envs, 1)
                step_data["rewards"] = rewards.reshape(1, num_envs, 1)
                step_data["dones"] = dones.reshape(1, num_envs, 1)
                rb.add(step_data, validate_args=cfg.buffer.validate_args)

                # host-side resets between steps (reference :357-374)
                prev_actions = (1.0 - dones) * actions_oh
                if reset_on_done and np.any(dones):
                    keep = 1.0 - dones  # numpy: carry stays on the player device
                    carry = (carry[0] * keep, carry[1] * keep)

                obs = next_obs
                for ep_rew, ep_len in episode_stats(info):
                    aggregator.update("Rewards/rew_avg", ep_rew)
                    aggregator.update("Game/ep_len_avg", ep_len)

        with telem.span("Time/train_time"):
            local = rb.buffer  # [T, N, ...]
            # mirror params: the recurrent carry lives on the player device,
            # and mixing it with mesh-committed params would be a device clash
            next_value = value_fn(
                mirror.current(),
                prepare_obs(obs, cnn_keys, mlp_keys, num_envs),
                prev_actions[None],
                carry,
            )
            returns, advantages = gae_fn(
                jnp.asarray(local["rewards"]),
                jnp.asarray(local["values"]),
                jnp.asarray(local["dones"]),
                next_value,
            )

            # chunk [T, N, ...] → sequence-major [C*N, L, ...]
            def to_seq(x: np.ndarray) -> np.ndarray:
                x = np.asarray(x)
                return (
                    x.reshape(num_chunks, seq_len, num_envs, *x.shape[2:])
                    .swapaxes(1, 2)
                    .reshape(num_sequences, seq_len, *x.shape[2:])
                )

            # in-sequence resets only when the rollout also reset the carry
            if reset_on_done:
                is_first = np.concatenate(
                    [np.zeros((1, num_envs, 1), np.float32), np.asarray(local["dones"][:-1])], axis=0
                )
            else:
                is_first = np.zeros((rollout_steps, num_envs, 1), np.float32)
            data = {k: jnp.asarray(to_seq(v)) for k, v in local.items()}
            data["is_first"] = jnp.asarray(to_seq(is_first))
            data["returns"] = jnp.asarray(to_seq(np.asarray(returns)))
            data["advantages"] = jnp.asarray(to_seq(np.asarray(advantages)))
            # initial recurrent state of each sequence = recorded pre-step
            # state at its first step; chunk-major [C, N, H] → [C*N, H] to
            # match to_seq's sequence ordering (s = chunk*N + env)
            data["cx0"] = jnp.asarray(np.stack(chunk_cx).reshape(num_sequences, H))
            data["hx0"] = jnp.asarray(np.stack(chunk_hx).reshape(num_sequences, H))
            data = {k: jax.device_put(v, dist.batch_sharding) for k, v in data.items()}

            coefs = update_coefs(cfg, update_iter, run.num_updates)
            root_key, up_key = jax.random.split(root_key)
            params, opt_state, metrics = update(params, opt_state, data, coefs, up_key)
            telem.record_grad_steps(num_minibatches * int(cfg.algo.update_epochs))
            mirror.refresh(params)  # blocking: next rollout acts with fresh params

        for k, v in metrics.items():
            aggregator.update(k, np.asarray(v))  # host-sync: ok (update cadence)

        if run.end_iteration(update_iter, learner):
            break

    def run_test(params):
        test_env = vectorize(
            Config({**cfg.to_dict(), "env": {**cfg.env.to_dict(), "num_envs": 1}}),
            cfg.seed,
            rank,
            log_dir,
        ).envs[0]
        test(module, params, test_env, cfg, log_dir, logger)

    run.close(learner, run_test if cfg.algo.run_test else None)


@register_evaluation(algorithms="ppo_recurrent")
def evaluate_ppo_recurrent(dist: Distributed, cfg: Config, state: Dict[str, Any]) -> None:
    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, dist.process_index)
    env = vectorize(cfg, cfg.seed, 0, log_dir).envs[0]
    root_key = dist.seed_everything(cfg.seed)
    module, params = build_agent(
        dist, cfg, env.observation_space, env.action_space, root_key, state["params"]
    )
    test(module, params, env, cfg, log_dir, logger)
