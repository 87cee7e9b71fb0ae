"""SAC — coupled off-policy training (Template B).

Reference sheeprl/algos/sac/sac.py (427 LoC). TPU-native re-design:

* `Ratio`-controlled gradient steps: the reference samples ONE big batch per
  iteration and slices it per gradient step (sac.py:300-337); here the
  [G, B, ...] batch crosses host→HBM once and the G gradient steps run as a
  single jitted `lax.scan` with donated carry (params of 3 optimizers +
  target EMA folded in — reference train() sac.py:32-75).
* alpha auto-tune: log_alpha is just another leaf in the params pytree; the
  grad all_reduce the reference does by hand (sac.py:72) falls out of the
  sharded jit.
* Target-critic EMA (`tau` polyak) happens inside the scan every
  `target_network_frequency` steps.
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
from ...engine import OverlapEngine, Packet, RecordingSink
from ...fleet import FleetEngine
from ...parallel import Distributed
from ...parallel.placement import make_param_mirror
from ...telemetry import Telemetry
from ...utils.checkpoint import CheckpointManager
from ...utils.env import episode_stats, probe_env_spaces, vectorize
from ...utils.logger import get_log_dir, get_logger
from ...utils.metric import MetricAggregator
from ...utils.registry import register_algorithm, register_evaluation
from ...resilience import RunGuard
from ...utils import run_info
from ...utils.utils import Ratio, save_configs
from .agent import SACActor, build_agent, sample_actions
from .loss import critic_loss, entropy_loss, policy_loss
from .utils import AGGREGATOR_KEYS, flatten_obs, prepare_obs, test


def make_train_fn(actor, critic, txs, cfg: Config, target_entropy: float):
    gamma = float(cfg.algo.gamma)
    tau = float(cfg.algo.tau)
    tnf = int(cfg.algo.critic.target_network_frequency)

    def one_step(carry, inp):
        params, opt_states = carry
        batch, key = inp

        # --- critic update ------------------------------------------------
        mean, log_std = actor.apply({"params": params["actor"]}, batch["next_observations"])
        key, k1 = jax.random.split(key)
        next_actions, next_logprobs = sample_actions(actor, mean, log_std, k1)
        target_q = critic.apply(
            {"params": params["target_critic"]}, batch["next_observations"], next_actions
        )  # [n, B, 1]
        min_target = jnp.min(target_q, axis=0) - jnp.exp(params["log_alpha"]) * next_logprobs
        # bootstrap through truncation: only true termination stops the return
        # (reference sac.py target uses data["terminated"], not dones)
        y = batch["rewards"] + (1.0 - batch["terminated"]) * gamma * min_target

        def qf_loss_fn(critic_params):
            q = critic.apply({"params": critic_params}, batch["observations"], batch["actions"])
            return critic_loss(q, jax.lax.stop_gradient(y), q.shape[0])

        qf_loss, qf_grads = jax.value_and_grad(qf_loss_fn)(params["critic"])
        updates, opt_states["critic"] = txs["critic"].update(
            qf_grads, opt_states["critic"], params["critic"]
        )
        params["critic"] = optax.apply_updates(params["critic"], updates)

        # --- actor update -------------------------------------------------
        def actor_loss_fn(actor_params):
            m, ls = actor.apply({"params": actor_params}, batch["observations"])
            key_a = jax.random.fold_in(key, 1)
            acts, logp = sample_actions(actor, m, ls, key_a)
            q = critic.apply({"params": params["critic"]}, batch["observations"], acts)
            min_q = jnp.min(q, axis=0)
            return policy_loss(jnp.exp(params["log_alpha"]), logp, min_q), logp

        (a_loss, logp), a_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(params["actor"])
        updates, opt_states["actor"] = txs["actor"].update(a_grads, opt_states["actor"], params["actor"])
        params["actor"] = optax.apply_updates(params["actor"], updates)

        # --- alpha update -------------------------------------------------
        def alpha_loss_fn(log_alpha):
            return entropy_loss(log_alpha, jax.lax.stop_gradient(logp), target_entropy)

        al_loss, al_grad = jax.value_and_grad(alpha_loss_fn)(params["log_alpha"])
        updates, opt_states["alpha"] = txs["alpha"].update(al_grad, opt_states["alpha"], params["log_alpha"])
        params["log_alpha"] = optax.apply_updates(params["log_alpha"], updates)

        # --- target EMA (reference sac.py:74-75 / agent.py qf_target update)
        step = opt_states["step"] + 1
        do_update = (step % tnf) == 0
        params["target_critic"] = jax.tree.map(
            lambda t, s: jnp.where(do_update, (1 - tau) * t + tau * s, t),
            params["target_critic"],
            params["critic"],
        )
        opt_states["step"] = step

        metrics = {
            "Loss/value_loss": qf_loss,
            "Loss/policy_loss": a_loss,
            "Loss/alpha_loss": al_loss,
        }
        return (params, opt_states), metrics

    @partial(jax.jit, donate_argnums=(0, 1))
    def train(params, opt_states, batches, keys):
        (params, opt_states), metrics = jax.lax.scan(one_step, (params, opt_states), (batches, keys))
        return params, opt_states, jax.tree.map(jnp.mean, metrics)

    return train


@register_algorithm(name="sac")
def main(dist: Distributed, cfg: Config) -> None:
    root_key = dist.seed_everything(cfg.seed)
    rank = dist.process_index
    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, rank)
    if rank == 0:
        save_configs(cfg, log_dir)

    # fleet mode (algo.fleet.workers > 0): env stepping lives in supervised
    # worker PROCESSES (sheeprl_tpu/fleet/) — the learner only needs the
    # spaces to build the agent, never its own vector env
    if FleetEngine.configured(cfg):
        envs = None
        obs_space, action_space = probe_env_spaces(cfg, cfg.seed, rank)
    else:
        envs = vectorize(cfg, cfg.seed, rank, log_dir)
        obs_space = envs.single_observation_space
        action_space = envs.single_action_space
    num_envs = int(cfg.env.num_envs)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    if not isinstance(action_space, gym.spaces.Box):
        raise RuntimeError("SAC requires a continuous (Box) action space")

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
            "step": jnp.zeros((), jnp.int32),
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
    cumulative_grad_steps = state["cumulative_grad_steps"] if state else 0

    # [G, B, ...] batches: HBM ring on a single accelerator, else
    # host-sampled + dp-sharded staging (data/device_ring.py)
    prefetch = make_uniform_prefetcher(
        cfg,
        dist,
        rb,
        batch_size,
        row_bytes_hint=estimate_row_bytes(obs_space, act_dim),
        emit=telem.emit,
    )
    pending_metrics: list = []
    # per-step inference on the player device (host CPU when the mesh is a
    # an accelerator); mirror re-syncs the actor after each train burst
    mirror, pdev, player_key, root_key = make_param_mirror(
        cfg, dist.local_device, {"actor": params["actor"]}, root_key
    )
    telem.emit(mirror.placement)

    if envs is not None:
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
            "cumulative_grad_steps": cumulative_grad_steps,
            "rng": root_key,
        }
        if cfg.buffer.checkpoint:
            s["rb"] = rb.checkpoint_state_dict()
        return s

    p_step = policy_step  # player-side env-step counter (== policy_step with the inline source)

    def interact(sink) -> None:
        """ONE vector env step (reference sac.py env block): act from the
        mirror snapshot, record the replay row into `sink`, a `RecordingSink`
        that rides a packet and is applied learner-side."""
        nonlocal obs_vec, player_key, p_step
        if p_step <= learning_starts:
            env_actions = np.stack([action_space.sample() for _ in range(num_envs)])
        else:
            player_key, k = jax.random.split(player_key)
            env_actions = np.asarray(
                act(mirror.current()["actor"], obs_vec, k)
            ).reshape(num_envs, act_dim)
        next_obs, rewards, terminated, truncated, info = envs.step(env_actions)
        p_step += num_envs

        # true next obs for the buffer: the final obs on done envs
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
            "dones": np.logical_or(terminated, truncated).astype(np.float32).reshape(1, num_envs, 1),
        }
        sink.add(step_data, validate_args=cfg.buffer.validate_args)
        obs_vec = flatten_obs(next_obs, mlp_keys, num_envs)

        for ep_rew, ep_len in episode_stats(info):
            # through the sink: the aggregator is not thread-safe, so these
            # ride the packet and land on the learner thread
            sink.stat("Rewards/rew_avg", ep_rew)
            sink.stat("Game/ep_len_avg", ep_len)

    def flush_logs() -> None:
        nonlocal last_log
        if policy_step - last_log >= cfg.metric.log_every or cfg.dry_run:
            with telem.span("Time/log_flush"):
                for m in pending_metrics:  # host-sync deferred to log cadence
                    for k, v in m.items():
                        aggregator.update(k, np.asarray(v))
                pending_metrics.clear()
                telem.log(
                    policy_step,
                    extra_metrics={"Params/replay_ratio": cumulative_grad_steps * dist.world_size / policy_step}
                    if policy_step > 0
                    else None,
                )
            last_log = policy_step

    def maybe_checkpoint() -> None:
        nonlocal last_checkpoint
        if (
            cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every
        ) or cfg.dry_run or policy_step >= total_steps:
            last_checkpoint = policy_step
            with telem.span("Time/checkpoint"):
                ckpt.save(policy_step, _ckpt_state())

    def play() -> Packet:  # the source times it under Time/env_interaction_time
        rec = RecordingSink()
        interact(rec)
        return Packet(rec, num_envs)

    # Who produces the packets the loop below consumes is decided here, once
    # (the protocol is in engine/overlap.py): supervised worker PROCESSES that
    # step the env slices, one ROUND (one packet per active worker, merged
    # full-width in worker order: apply_concat) per num_envs quantum; else
    # `play` on a player thread beside this one, or inline on this thread
    # (`algo.overlap.enabled`).
    source = FleetEngine.setup(cfg, telem, guard, total_steps=total_steps, initial_step=policy_step)
    if source.enabled:
        source.start("sheeprl_tpu.fleet.programs:sac_program", num_envs, cfg, apply=FleetEngine.apply_concat)
        source.published(mirror.current())  # v1: the workers act with these
    else:
        source = OverlapEngine.setup(
            cfg, telem, guard, total_steps=total_steps, initial_step=policy_step
        ).start(play)
    stopped = False
    while policy_step < total_steps:
        telem.tick(policy_step)
        if guard.stop_reached(policy_step, total_steps, None, save=False):
            stopped = True
            break
        packets = source.take()
        if not packets:
            break
        # ack packets in FIFO order, one Ratio call per packet at the true
        # cumulative step: the ledger is the same whichever source fed it
        gs = []
        taken = sum(pkt.env_steps for pkt in packets)
        with telem.span("Time/learner_apply", env_steps=taken, packets=len(packets)):
            for pkt in packets:
                pkt.apply(rb, aggregator)
                policy_step += pkt.env_steps
                if policy_step >= learning_starts:
                    g = ratio(policy_step / dist.world_size)
                    telem.record_grad_steps(g)
                    gs.append(g)
        bursting = False
        for i, g in enumerate(gs):
            if g <= 0:
                continue
            with telem.span("Time/train_time", grad_steps=g, burst=source.burst):
                bursting = True
                batches = prefetch.take(g)  # [G, B, ...]
                root_key, sub = jax.random.split(root_key)
                params, opt_states, metrics = train(params, opt_states, batches, jax.random.split(sub, g))
                cumulative_grad_steps += g
            # held on device until log time; not at all when metrics are off
            if not MetricAggregator.disabled:
                pending_metrics.append(metrics)
            nxt = next((x for x in gs[i + 1 :] if x > 0), 0)
            if nxt > 0:
                with telem.span("Time/replay_stage"):
                    prefetch.stage(nxt)
        if bursting:
            mirror.refresh({"actor": params["actor"]})
            run_info.mark_steady(policy_step, sync=lambda: jax.block_until_ready(metrics))
        # every iteration: releases take()'s claim (a fleet is sent the params)
        source.published(mirror.current() if bursting else None)
        if policy_step < total_steps:
            # the next packet is as large as this one (a degraded fleet round is smaller)
            with telem.span("Time/replay_stage"):
                prefetch.stage(ratio.peek((policy_step + packets[-1].env_steps) / dist.world_size))
        flush_logs()
        maybe_checkpoint()
    # drain: what the source had queued lands in the buffer, so the final
    # checkpoint is consistent (the ratio catches up at resume)
    policy_step += source.shutdown(lambda pkt: pkt.apply(rb, aggregator))
    # an early exit (wall cap, or a fleet whose every worker is quarantined)
    # still leaves a resumable checkpoint; preemption saves through the guard
    if (stopped or policy_step < total_steps) and not guard.preempted and cfg.checkpoint.save_last:
        ckpt.save(policy_step, _ckpt_state())

    guard.close(policy_step, _ckpt_state)
    if envs is not None:
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


@register_evaluation(algorithms=["sac", "sac_decoupled"])
def evaluate_sac(dist: Distributed, cfg: Config, state: Dict[str, Any]) -> None:
    """Reference sac/evaluate.py:15 (registered for sac AND sac_decoupled).
    Routed through the serving subsystem's `InferencePolicy`
    (serve/evaluate.py) — evaluation and serving share one
    checkpoint→policy path; the decoupled trainer checkpoints the same
    {params} pytree."""
    from ...serve.evaluate import evaluate_with_policy

    evaluate_with_policy(dist, cfg, state)
