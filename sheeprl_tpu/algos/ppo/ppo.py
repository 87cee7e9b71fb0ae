"""PPO — coupled on-policy training (Template A).

TPU-native re-design of reference sheeprl/algos/ppo/ppo.py (452 LoC):

* rollout on host (CPU envs) with a single jitted `act` fn — the only
  per-step device work (SURVEY.md §7 host↔device-boundary risk);
* GAE as a reverse `lax.scan` on device (reference python loop utils.py:63);
* the whole update phase — `update_epochs` × minibatches with in-jit
  permutations — is ONE jitted, donated-argument XLA program
  (reference ppo.py:52-102 dispatches one torch step per minibatch);
* data parallelism: params replicated / batch sharded over the `dp` mesh
  axis; XLA inserts the gradient all-reduce (replaces Fabric DDP,
  reference ppo.py:93).
* `buffer.share_data` (reference ppo.py:362-369 all_gather) is implicit:
  the single JAX controller already sees every env's data.

LR / clip / entropy annealing (reference ppo.py:414-424) is passed as traced
scalars so annealing never retraces.
"""
from __future__ import annotations

import os
import time
from functools import partial
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from ...config import Config, instantiate
from ...data import ReplayBuffer
from ...engine import OverlapEngine, Packet
from ...fleet import FleetEngine
from ...fleet.programs import merge_ppo_round
from ...ops import gae as gae_op
from ...optim import clipped
from ...parallel import Distributed
from ...parallel.placement import make_param_mirror
from ...utils.checkpoint import CheckpointManager
from ...utils.env import episode_stats, probe_env_spaces, vectorize
from ...telemetry import Telemetry
from ...telemetry import xla as _xla
from ...utils.logger import get_log_dir, get_logger
from ...utils.registry import register_algorithm, register_evaluation
from ...resilience import RunGuard
from ...utils import run_info
from ...utils.utils import Ratio, linear_annealing, save_configs
from .agent import PPOAgent, actions_and_log_probs, build_agent
from .loss import entropy_loss, policy_loss, value_loss
from .utils import AGGREGATOR_KEYS, prepare_obs, test


# unique retrace-detector tags per maker call: two runs in one process must
# not read each other's trace history as retraces
_PPO_TAG = iter(range(1 << 30))


def make_act_fn(module: PPOAgent):
    def act(params, obs, key):
        actor_out, value = module.apply({"params": params}, obs)
        actions, logprob, _ = actions_and_log_probs(actor_out, module.is_continuous, key=key)
        return actions, logprob, value

    # instrumented pre-jit: retraces are attributed and compile seconds land
    # under this tag in the per-function breakdown
    return jax.jit(_xla.RETRACE_DETECTOR.wrap(act, f"ppo.act#{next(_PPO_TAG)}"))


def make_value_fn(module: PPOAgent):
    def value_fn(params, obs):
        _, value = module.apply({"params": params}, obs)
        return value

    return jax.jit(_xla.RETRACE_DETECTOR.wrap(value_fn, f"ppo.value#{next(_PPO_TAG)}"))


def make_update_fn(module: PPOAgent, tx, cfg: Config, num_minibatches: int, mb_size: int):
    """The whole PPO update (epochs × minibatches) as one jitted program."""
    update_epochs = int(cfg.algo.update_epochs)
    clip_vloss = bool(cfg.algo.clip_vloss)
    normalize_advantages = bool(cfg.algo.normalize_advantages)
    reduction = str(cfg.algo.loss_reduction)

    def loss_fn(params, mb: Dict[str, jax.Array], coefs: Dict[str, jax.Array]):
        obs = {k[4:]: v for k, v in mb.items() if k.startswith("obs:")}
        actor_out, new_values = module.apply({"params": params}, obs)
        actions = mb["actions"]
        if not module.is_continuous:
            actions = actions.astype(jnp.int32)
        _, new_logprobs, entropy = actions_and_log_probs(
            actor_out, module.is_continuous, actions=actions
        )
        advantages = mb["advantages"]
        if normalize_advantages:
            advantages = (advantages - jnp.mean(advantages)) / (jnp.std(advantages) + 1e-8)
        pg_loss = policy_loss(
            new_logprobs, mb["logprobs"], advantages, coefs["clip_coef"], reduction
        )
        v_loss = value_loss(
            new_values, mb["values"], mb["returns"], coefs["clip_coef"], clip_vloss, reduction
        )
        ent_loss = entropy_loss(entropy, reduction)
        loss = pg_loss + coefs["vf_coef"] * v_loss + coefs["ent_coef"] * ent_loss
        return loss, {"Loss/policy_loss": pg_loss, "Loss/value_loss": v_loss, "Loss/entropy_loss": ent_loss}

    def update(params, opt_state, data: Dict[str, jax.Array], coefs, key):
        batch = next(iter(data.values())).shape[0]

        def epoch_step(carry, _):
            params, opt_state, key = carry
            key, pk = jax.random.split(key)
            perm = jax.random.permutation(pk, batch)
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

    return jax.jit(
        _xla.RETRACE_DETECTOR.wrap(update, f"ppo.update#{next(_PPO_TAG)}"), donate_argnums=(0, 1)
    )


@register_algorithm(name="ppo")
def main(dist: Distributed, cfg: Config) -> None:
    root_key = dist.seed_everything(cfg.seed)
    rank = dist.process_index
    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, rank)
    if rank == 0:
        save_configs(cfg, log_dir)

    # fleet mode (algo.fleet.workers > 0): rollout collection lives in
    # supervised worker PROCESSES (sheeprl_tpu/fleet/) — one rollout slice
    # per worker per publication, merged full-width learner-side
    if FleetEngine.configured(cfg):
        envs = None
        obs_space, action_space = probe_env_spaces(cfg, cfg.seed, rank)
    else:
        envs = vectorize(cfg, cfg.seed, rank, log_dir)
        obs_space = envs.single_observation_space
        action_space = envs.single_action_space
    num_envs = int(cfg.env.num_envs)
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys
    if not isinstance(obs_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {obs_space}")

    # -- resume ------------------------------------------------------------
    state = None
    if cfg.checkpoint.resume_from:
        state = CheckpointManager.load(cfg.checkpoint.resume_from)

    root_key, init_key = jax.random.split(state["rng"] if state else root_key)
    module, params = build_agent(
        dist, cfg, obs_space, action_space, init_key, state["params"] if state else None
    )

    tx = clipped(instantiate(cfg.algo.optimizer), cfg.algo.get("max_grad_norm", 0.0))
    opt_state = dist.replicate(state["opt_state"] if state else tx.init(params))

    rollout_steps = int(cfg.algo.rollout_steps)
    rb = ReplayBuffer(
        rollout_steps,
        num_envs,
        obs_keys=obs_keys,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}") if cfg.buffer.memmap else None,
        seed=cfg.seed + 1024 * rank,
    )

    total_batch = rollout_steps * num_envs
    mb_size = int(cfg.algo.per_rank_batch_size) * dist.world_size
    if total_batch % mb_size != 0:
        raise ValueError(
            f"rollout_steps*num_envs ({total_batch}) must be divisible by "
            f"per_rank_batch_size*world_size ({mb_size})"
        )
    num_minibatches = total_batch // mb_size

    act = make_act_fn(module)
    value_fn = make_value_fn(module)
    update = make_update_fn(module, tx, cfg, num_minibatches, mb_size)
    # per-step inference runs on the player device (host CPU when the mesh is
    # an accelerator — parallel/placement.py); blocking refresh after
    # every update keeps PPO strictly on-policy. NOT `in_order`: with the
    # threaded source (`algo.overlap.enabled`) the player thread may act beside
    # a donating update, so on the learner's device the mirror keeps a copy
    mirror, pdev, player_key, root_key = make_param_mirror(
        cfg, dist.local_device, params, root_key, allow_async=False
    )
    gae_fn = jax.jit(partial(gae_op, num_steps=rollout_steps, gamma=cfg.algo.gamma, gae_lambda=cfg.algo.gae_lambda))

    telem = Telemetry.setup(cfg, log_dir, rank, logger=logger, aggregator_keys=AGGREGATOR_KEYS)
    aggregator = telem.aggregator
    telem.emit(mirror.placement)
    roofline_done: list = []  # one-shot latch for the update's lowering
    ckpt = CheckpointManager(log_dir, keep_last=cfg.checkpoint.keep_last, enabled=rank == 0)
    guard = RunGuard.setup(cfg, ckpt, telem, log_dir)
    ckpt = guard.ckpt

    # -- counters ----------------------------------------------------------
    policy_steps_per_iter = num_envs * rollout_steps
    num_updates = int(cfg.algo.total_steps) // policy_steps_per_iter if not cfg.dry_run else 1
    start_iter = (state["update"] + 1) if state else 1
    policy_step = state["policy_step"] if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0

    if envs is not None:
        obs, _ = envs.reset(seed=cfg.seed)

    def _ckpt_state():
        # `completed_update` = the last update whose params this checkpoint
        # carries (resume restarts at +1). Both loops can break at
        # the TOP of an iteration (preemption/wall-cap before the update
        # ran), so the loop counter itself would over-count by one there.
        return {
            "params": params,
            "opt_state": opt_state,
            "update": completed_update,
            "policy_step": policy_step,
            "last_log": last_log,
            "last_checkpoint": last_checkpoint,
            "rng": root_key,
        }

    def rollout(buf):
        """One rollout_steps collection (reference ppo.py:232-312): acts
        with the mirror snapshot, fills `buf`, and returns
        ``(local [T, N, ...] dict, bootstrap next_value, episode stats)``.
        Runs on the learner's thread with the inline source, on the player
        thread otherwise (everything it touches — envs, mirror, rollout
        buffer, player_key — is player-owned; episode stats are RETURNED,
        not aggregated, because the aggregator is not thread-safe and its
        writes must stay on the learner thread)."""
        nonlocal obs, player_key
        ep_stats = []
        for _ in range(rollout_steps):
            device_obs = prepare_obs(obs, cnn_keys, mlp_keys, num_envs)
            player_key, act_key = jax.random.split(player_key)
            actions, logprobs, values = act(mirror.current(), device_obs, act_key)
            np_actions = np.asarray(actions)
            if module.is_continuous:
                env_actions = np_actions.reshape(num_envs, -1)
            elif isinstance(action_space, gym.spaces.MultiDiscrete):
                env_actions = np_actions.reshape(num_envs, -1)
            else:
                env_actions = np_actions.reshape(num_envs)
            next_obs, rewards, terminated, truncated, info = envs.step(env_actions)

            rewards = np.asarray(rewards, dtype=np.float32).reshape(num_envs, 1)
            dones = np.logical_or(terminated, truncated).astype(np.float32).reshape(num_envs, 1)

            # truncation bootstrapping (reference ppo.py:286-305)
            if np.any(truncated) and "final_obs" in info:
                final_obs = info["final_obs"]
                trunc_idx = np.nonzero(truncated)[0]
                stacked = {
                    k: np.stack([np.asarray(final_obs[i][k]) for i in trunc_idx])
                    for k in obs_keys
                }
                vals = np.asarray(
                    value_fn(
                        mirror.current(),
                        prepare_obs(stacked, cnn_keys, mlp_keys, len(trunc_idx)),
                    )
                )
                rewards[trunc_idx] += cfg.algo.gamma * vals.reshape(-1, 1)

            step_data: Dict[str, np.ndarray] = {}
            for k in obs_keys:
                step_data[f"obs:{k}"] = np.asarray(obs[k]).reshape(1, num_envs, *obs_space[k].shape)
            step_data["actions"] = np_actions.reshape(1, num_envs, -1).astype(np.float32)
            step_data["logprobs"] = np.asarray(logprobs).reshape(1, num_envs, 1)
            step_data["values"] = np.asarray(values).reshape(1, num_envs, 1)
            step_data["rewards"] = rewards.reshape(1, num_envs, 1)
            step_data["dones"] = dones.reshape(1, num_envs, 1)
            buf.add(step_data, validate_args=cfg.buffer.validate_args)

            obs = next_obs

            ep_stats.extend(episode_stats(info))
        # mirror params: the bootstrap value runs on the player device like
        # the rollout's other forwards (data is tiny [T, N])
        next_value = value_fn(mirror.current(), prepare_obs(obs, cnn_keys, mlp_keys, num_envs))
        return buf.buffer, next_value, ep_stats

    def record_ep_stats(ep_stats) -> None:
        if aggregator is not None:
            for ep_rew, ep_len in ep_stats:
                aggregator.update("Rewards/rew_avg", ep_rew)
                aggregator.update("Game/ep_len_avg", ep_len)

    def update_from(local, next_value, update_iter):
        """GAE + the whole jitted update for one rollout (learner side)."""
        nonlocal params, opt_state, root_key
        returns, advantages = gae_fn(
            jnp.asarray(local["rewards"]),
            jnp.asarray(local["values"]),
            jnp.asarray(local["dones"]),
            jnp.asarray(next_value),
        )

        data = {k: jnp.asarray(v).reshape(total_batch, *v.shape[2:]) for k, v in local.items()}
        data["returns"] = returns.reshape(total_batch, 1)
        data["advantages"] = advantages.reshape(total_batch, 1)
        data = {k: jax.device_put(v, dist.batch_sharding) for k, v in data.items()}

        # anneal (traced scalars → no retrace; reference ppo.py:414-424)
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
        if not roofline_done:
            roofline_done.append(True)
            # one-time roofline verdict for the whole jitted update: lower()
            # only traces (donated args are untouched), and the facade
            # re-emits the verdict each log interval with the measured
            # grad-step rate as the attained-fraction series
            try:
                # lowering only needs the key's aval, so a dummy key keeps
                # the training RNG stream untouched; the deliberate re-trace
                # must not count as a retrace
                with _xla.suppress_retrace_accounting():
                    lowered = update.lower(params, opt_state, data, coefs, jax.random.PRNGKey(0))
                telem.register_roofline(
                    "train_step", lowered=lowered, role="learner", track_grad_rate=True
                )
            except Exception:
                pass
        params, opt_state, metrics = update(params, opt_state, data, coefs, up_key)
        telem.record_grad_steps(num_minibatches * int(cfg.algo.update_epochs))
        return metrics

    def flush_logs() -> None:
        nonlocal last_log
        if policy_step - last_log >= cfg.metric.log_every or cfg.dry_run:
            telem.log(policy_step)
            last_log = policy_step

    def maybe_checkpoint(update_iter) -> None:
        nonlocal last_checkpoint
        if (
            cfg.checkpoint.every > 0
            and policy_step - last_checkpoint >= cfg.checkpoint.every
        ) or cfg.dry_run or update_iter == num_updates:
            last_checkpoint = policy_step
            ckpt.save(policy_step, _ckpt_state())

    fleet = FleetEngine.setup(
        cfg,
        telem,
        guard,
        total_steps=num_updates * policy_steps_per_iter,
        initial_step=policy_step,
    )
    update_iter = start_iter
    completed_update = start_iter - 1
    if fleet.enabled:
        # ---- supervised actor-fleet loop (sheeprl_tpu/fleet/): each worker
        # collects ONE rollout slice per param publication (strict on-policy
        # round protocol — the fleet twin of the player thread's
        # staleness_bound=0 mode), merged full-width learner-side. A
        # quarantined worker's columns are backfilled by duplicating
        # surviving slices so the jitted update's shapes never change.
        fleet.start("sheeprl_tpu.fleet.programs:ppo_program", num_envs, cfg)
        fleet.publish(mirror.current())  # v1 releases the first rollouts
        stopped = False
        while update_iter <= num_updates:
            telem.tick(policy_step)
            if guard.stop_reached(policy_step, int(cfg.algo.total_steps), None, save=False):
                stopped = True
                break
            with telem.span("Time/env_interaction_time"):
                # strict protocol: only rollouts acted with the NEWEST
                # publication merge; a post-crash duplicate for an older
                # version is dropped, not silently trained on
                rnd = fleet.take_round(policy_step, min_version=fleet.pub_version)
            if rnd is None:
                break
            t_merge0 = time.time()
            local, next_value, ep_stats = merge_ppo_round(rnd, fleet.workers)
            fleet.mark_applied(rnd, t_merge0)
            policy_step += rnd.env_steps
            record_ep_stats(ep_stats)
            with telem.span("Time/train_time"):
                metrics = update_from(local, next_value, update_iter)
                mirror.refresh(params)  # blocking: the next rollouts act with these
                fleet.publish(mirror.current())  # releases the parked workers
                run_info.mark_steady(policy_step)
            completed_update = update_iter
            if aggregator is not None:
                for k, v in metrics.items():
                    aggregator.update(k, np.asarray(v))  # host-sync: ok (update cadence)
            flush_logs()
            maybe_checkpoint(update_iter)
            update_iter += 1
        # queued rollouts (collected for params that will never act again)
        # are dropped — PPO keeps no cross-update buffer, same as in-process
        fleet.shutdown()
        if (stopped or update_iter <= num_updates) and not guard.preempted and cfg.checkpoint.save_last:
            ckpt.save(policy_step, _ckpt_state())
    else:
        # ---- in-process loop: `play` is one rollout, which the engine runs
        # on a player thread beside this one or inline on this thread
        # (`algo.overlap.enabled`, engine/overlap.py). Threaded with
        # staleness_bound 0 (the default) the player waits for each update to
        # publish and acts with the params the inline source acts with; with
        # bound 1 it collects rollout k+1 against the pre-update snapshot
        # while the learner updates on rollout k.
        # Ping-pong rollout buffers instead of a per-update deep copy: the
        # engine's pre-collection backpressure refills a buffer only after
        # the learner has consumed the packet `run_ahead` packets back, so
        # run_ahead+1 buffers cycled round-robin are race-free (inline nothing
        # runs ahead: `rb` alone).
        engine = OverlapEngine.setup(
            cfg,
            telem,
            guard,
            total_steps=num_updates * policy_steps_per_iter,
            initial_step=policy_step,
            default_queue_depth=1,  # at most one rollout ahead of the learner
        )
        bufs = [rb] + [
            ReplayBuffer(
                rollout_steps,
                num_envs,
                obs_keys=obs_keys,
                memmap=cfg.buffer.memmap,
                memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}_overlap{i}")
                if cfg.buffer.memmap
                else None,
                seed=cfg.seed + 1024 * rank + 7 * (i + 1),
            )
            for i in range(engine.run_ahead)
        ]
        buf_idx = [0]

        def play() -> Packet:  # the engine times it under Time/env_interaction_time
            buf = bufs[buf_idx[0] % len(bufs)]
            buf_idx[0] += 1
            local, next_value, ep_stats = rollout(buf)
            return Packet((local, np.asarray(next_value), ep_stats), policy_steps_per_iter)

        engine.start(play)
        stopped = False
        while update_iter <= num_updates:
            telem.tick(policy_step)
            if guard.stop_reached(policy_step, int(cfg.algo.total_steps), None, save=False):
                stopped = True
                break
            pkts = engine.take(max_packets=1)
            if not pkts:
                break
            local, next_value, ep_stats = pkts[0].payload
            policy_step += pkts[0].env_steps
            record_ep_stats(ep_stats)  # learner-thread aggregator writes only
            with telem.span("Time/train_time"):
                metrics = update_from(local, next_value, update_iter)
                mirror.refresh(params)  # blocking: the next rollout acts with these
                engine.published()  # release take()'s claim: unblocks a strict player
                run_info.mark_steady(policy_step)
            completed_update = update_iter
            if aggregator is not None:
                for k, v in metrics.items():
                    aggregator.update(k, np.asarray(v))  # host-sync: ok (update cadence)
            flush_logs()
            maybe_checkpoint(update_iter)
            update_iter += 1
        # a queued rollout (collected for params that will never act again)
        # is dropped: PPO keeps no cross-update buffer to stay consistent
        engine.shutdown()
        if stopped and not guard.preempted and cfg.checkpoint.save_last:
            ckpt.save(policy_step, _ckpt_state())

    guard.close(policy_step, _ckpt_state)
    if envs is not None:
        envs.close()
    telem.close(policy_step)
    if rank == 0 and cfg.algo.run_test:
        test_env = vectorize(
            Config({**cfg.to_dict(), "env": {**cfg.env.to_dict(), "num_envs": 1}}),
            cfg.seed,
            rank,
            log_dir,
        ).envs[0]
        test(module, params, test_env, cfg, log_dir, logger)
    if rank == 0 and not cfg.model_manager.disabled:
        from ...utils.model_manager import register_model

        register_model(cfg, {"agent": params}, log_dir)
    if logger is not None:
        logger.close()


@register_evaluation(algorithms=["ppo", "ppo_decoupled"])
def evaluate_ppo(dist: Distributed, cfg: Config, state: Dict[str, Any]) -> None:
    """Reference ppo/evaluate.py:15 and :58. Routed through the serving
    subsystem's `InferencePolicy` (serve/evaluate.py), so evaluation and
    `sheeprl_tpu serve` share one checkpoint→policy path; the decoupled
    trainer saves the same {params} pytree, so one eval covers both."""
    from ...serve.evaluate import evaluate_with_policy

    evaluate_with_policy(dist, cfg, state)
