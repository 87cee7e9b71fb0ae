"""DreamerV3 — world-model RL, the flagship workload (Template B).

Reference sheeprl/algos/dreamer_v3/dreamer_v3.py (780 LoC). TPU-native
re-design of the train step (reference train() :48-357):

* HOT LOOP 1 (dynamic learning, reference python loop :115-145) is a
  `lax.scan` over time of the fused RSSM cell;
* HOT LOOP 2 (imagination, :235-241) is a second scan over the horizon;
* the whole gradient step — world model, actor (with the imagination rollout
  inside its loss for dynamics backprop), critic, Moments update and
  target-critic EMA — is ONE jitted, donated-argument XLA program;
* per-env partial resets are masked updates inside the jitted player step,
  not python indexing (SURVEY.md §7 risk list);
* the recurrent player state (h, z, a) lives on device between env steps.

Losses run in f32; `Moments` normalization happens inside the jit.
"""
from __future__ import annotations

import os
import sys
import time
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
from ...engine import OverlapEngine, Packet, RecordingSink
from ...fleet import FleetEngine
from ...distributions import (
    BernoulliSafeMode,
    Independent,
    OneHotCategoricalStraightThrough,
    TwoHotEncodingDistribution,
)
from ...ops import lambda_values as lambda_values_op
from ...ops import pallas_gru as pg
from ...ops import wgrad_hoist
from ...ops.transforms import unrolled_cumprod
from ...optim import clipped
from ...parallel import Distributed
from ...parallel.mesh import maybe_shard_opt_state, maybe_shard_params
from ...parallel.placement import make_param_mirror, player_device, read_subtree
from ...telemetry import Telemetry
from ...telemetry.spans import call_counts
from ...telemetry import xla as _xla
from ...utils.checkpoint import CheckpointManager
from ...utils.env import episode_stats, patch_restarted_envs, probe_env_spaces, vectorize
from ...utils.logger import get_log_dir, get_logger
from ...utils.metric import MetricAggregator
from ...utils.registry import register_algorithm, register_evaluation
from ...resilience import RunGuard
from ...utils import run_info
from ...utils.utils import Ratio, acknowledge_partial_donation, save_configs
from .agent import Actor, WorldModel, build_agent, compute_stochastic_state, sample_actor_actions
from .loss import reconstruction_loss
from .utils import (
    AGGREGATOR_KEYS,
    MomentsState,
    decode_obs_dists,
    extract_masks,
    init_moments,
    make_precision_applies,
    normalize_obs,
    prepare_obs,
    test,
    update_moments,
    use_phase_obs_loss,
)


def build_optimizers(cfg: Config, params):
    """Clipped wm/actor/critic optax transforms + fresh opt states (shared by
    the train loop, bench_dv3.py and __graft_entry__.py so the measured
    program is exactly the training program)."""
    txs = {
        "wm": clipped(instantiate(cfg.algo.world_model.optimizer), cfg.algo.world_model.clip_gradients),
        "actor": clipped(instantiate(cfg.algo.actor.optimizer), cfg.algo.actor.clip_gradients),
        "critic": clipped(instantiate(cfg.algo.critic.optimizer), cfg.algo.critic.clip_gradients),
    }
    opt_states = {
        "wm": txs["wm"].init(params["wm"]),
        "actor": txs["actor"].init(params["actor"]),
        "critic": txs["critic"].init(params["critic"]),
        "step": jnp.zeros((), jnp.int32),
    }
    return txs, opt_states


def make_train_fn(
    wm: WorldModel,
    actor: Actor,
    critic,
    txs,
    cfg: Config,
    is_continuous: bool,
    actions_dim: Sequence[int],
    state_shardings: Any = None,
    emit: Any = None,
):
    """The jitted train function. ``emit`` (``telem.emit``) takes the run's
    ``wgrad_hoist`` event when the function is traced. ``state_shardings``
    is the sharding tree
    of ``(params, opt_states, moments)`` as the loop placed them: the step
    then hands the state back placed the same way. Left to itself the
    compiler picks output shardings of its own on a multi-axis mesh (at S
    widths under dp2 x fsdp2 it returns 91 of 236 replicated leaves
    fsdp-sharded), the second call sees other input shardings than the
    first, and the whole program compiles again."""
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    wm_cfg = cfg.algo.world_model
    stoch_flat = int(wm_cfg.stochastic_size) * int(wm_cfg.discrete_size)
    decoupled = bool(wm_cfg.select("decoupled_rssm") or False)
    R = int(wm_cfg.recurrent_model.recurrent_state_size)
    # mixed precision (reference: Fabric's precision plugin) — shared cast
    # boundary, utils.make_precision_applies
    wm_apply, actor_apply, critic_apply, _cast, compute_dtype, mixed = make_precision_applies(
        cfg, wm, actor, critic
    )
    # Pallas scan-resident GRU (ops/pallas_gru.py): True is the compiled TPU
    # kernel, "interpret" the interpreter (what the CPU tests run). Asking
    # for it where it cannot run is an error, never a quiet scan: only the
    # decoupled path qualifies (its GRU inputs are time-parallel), the
    # kernel computes in f32, and the fused weight block must fit VMEM.
    pallas_mode = wm_cfg.select("pallas_gru") or False
    if pallas_mode not in (False, True, "interpret"):
        raise ValueError(
            f"algo.world_model.pallas_gru must be False|True|interpret, got {pallas_mode!r}"
        )
    use_pallas = bool(pallas_mode)
    if use_pallas:
        if not decoupled:
            raise ValueError("algo.world_model.pallas_gru needs algo.world_model.decoupled_rssm=True")
        if mixed:
            raise ValueError(
                "algo.world_model.pallas_gru computes in f32: use fabric.precision=32-true"
            )
        if not pg.fits_vmem(int(wm_cfg.recurrent_model.dense_units), R):
            raise ValueError(
                "algo.world_model.pallas_gru: the fused GRU weights "
                f"([{int(wm_cfg.recurrent_model.dense_units)}+{R}, 3*{R}] f32) exceed the "
                "kernel's VMEM budget (XS and S presets fit)"
            )
    pallas_interpret = pallas_mode == "interpret"
    # phase-space observation loss rides the einsum decoder (see decode_phases)
    phase_obs_loss = use_phase_obs_loss(wm_cfg, cnn_keys)
    horizon = int(cfg.algo.horizon)
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    ent_coef = float(cfg.algo.actor.ent_coef)
    tau = float(cfg.algo.critic.tau)
    target_freq = int(cfg.algo.critic.per_rank_target_network_update_freq)
    moments_cfg = cfg.algo.actor.moments

    # The two sequence scans of the world model, as steps of
    # `ops.wgrad_hoist.scan`: the gradient of every Dense kernel the step
    # applies is one [T*B]-row matmul after the backward scan, which then
    # carries (h, z) and vectors but no array of a kernel's shape.
    def dyn_step(wm_params, perturbations, carry, e, held):
        h, z = carry
        a, first, k = held
        (h, z, post_logits, prior_logits), taped = wm_apply(
            wm_params, WorldModel.dynamic, z, h, a, e, first, k, tape=True, perturbations=perturbations
        )
        return (h, z), (h, z, post_logits, prior_logits), taped

    def dyn_step_dec(wm_params, perturbations, h, z_in, held):
        a, first = held
        (h, prior_logits), taped = wm_apply(
            wm_params, WorldModel.dynamic_decoupled, z_in, h, a, first, tape=True, perturbations=perturbations
        )
        return h, (h, prior_logits), taped

    reported = []

    def report_hoist(scan, hoisted):
        # while tracing: once for this train function, however often it traces
        if emit is not None and not reported:
            reported.append(scan)
            emit({"event": "wgrad_hoist", "scan": scan, **hoisted})

    def one_step(params, opt_states, moments: MomentsState, batch, key):
        T, B = batch["rewards"].shape[:2]
        k_dyn, k_img, k_act0 = jax.random.split(key, 3)
        batch_obs = normalize_obs({k: batch[k] for k in cnn_keys + mlp_keys}, cnn_keys)
        is_first = batch["is_first"].at[0].set(1.0)
        batch_actions = jnp.concatenate(
            [jnp.zeros_like(batch["actions"][:1]), batch["actions"][:-1]], axis=0
        )

        # ---------------- world model ------------------------------------
        def wm_loss_fn(wm_params):
            with jax.named_scope("wm_encoder"):
                embedded = wm_apply(wm_params, WorldModel.embed, batch_obs)  # [T, B, E]

            with jax.named_scope("wm_rssm"):
                if decoupled:
                    # DecoupledRSSM (reference dreamer_v3.py:115-129): posterior
                    # logits for the WHOLE sequence in one time-parallel MLP —
                    # only h + prior stay sequential. The posterior driving the
                    # recurrent model at step i is the step i-1 sample (zeros at
                    # i=0, reference :118-121).
                    post_logits = wm_apply(wm_params, WorldModel.representation_logits, embedded)
                    zs = compute_stochastic_state(
                        post_logits, int(wm_cfg.discrete_size), k_dyn
                    ).reshape(T, B, stoch_flat)
                    z_prev = jnp.concatenate([jnp.zeros_like(zs[:1]), zs[:-1]], axis=0)

                    if use_pallas:
                        # everything around the recurrence is time-parallel: the
                        # is_first masking of (z, a), the pre-GRU feature matmul
                        # and the prior head all batch over T; only the GRU runs
                        # sequentially — inside the VMEM-resident Pallas kernel
                        h0_row, z0_row = wm_apply(
                            wm_params, WorldModel.initial_states, (B,)
                        )
                        z_in = (1 - is_first) * z_prev + is_first * z0_row[None]
                        a_in = (1 - is_first) * batch_actions
                        feats = wm_apply(
                            wm_params,
                            WorldModel.recurrent_features,
                            jnp.concatenate([z_in, a_in], -1),
                        )
                        gru_p = wm_params["rssm"]["recurrent_model"]["gru"]
                        ln_p = gru_p["LayerNorm_0"]["LayerNorm_0"]
                        hs = pg.gru_sequence(
                            feats,
                            is_first,
                            h0_row,
                            gru_p["fused"]["kernel"],
                            ln_p["scale"],
                            ln_p["bias"],
                            pallas_interpret,
                        )
                        prior_logits = wm_apply(wm_params, WorldModel.transition_logits, hs)
                    else:
                        _, (hs, prior_logits) = wgrad_hoist.scan(
                            dyn_step_dec,
                            {"rssm": wm_params["rssm"]},
                            jnp.zeros((B, R)),
                            z_prev,
                            (batch_actions, is_first),
                            report=partial(report_hoist, "decoupled"),
                        )
                else:
                    _, (hs, zs, post_logits, prior_logits) = wgrad_hoist.scan(
                        dyn_step,
                        {"rssm": wm_params["rssm"]},
                        (jnp.zeros((B, R)), jnp.zeros((B, stoch_flat))),
                        embedded,
                        (batch_actions, is_first, jax.random.split(k_dyn, T)),
                        report=partial(report_hoist, "coupled"),
                    )
            latents = jnp.concatenate([zs, hs], axis=-1)
            with jax.named_scope("wm_decoder"):
                po, obs_targets = decode_obs_dists(
                    wm_apply, wm_params, WorldModel, latents, batch_obs, cnn_keys, mlp_keys, phase_obs_loss
                )
            with jax.named_scope("wm_heads"):
                pr = TwoHotEncodingDistribution(wm_apply(wm_params, WorldModel.reward, latents), dims=1)
                pc = Independent(
                    BernoulliSafeMode(logits=wm_apply(wm_params, WorldModel.cont, latents)), 1
                )
                continues_targets = 1 - batch["terminated"]
                S, D = int(wm_cfg.stochastic_size), int(wm_cfg.discrete_size)
                rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = reconstruction_loss(
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

        (wm_loss, wm_aux), wm_grads = jax.value_and_grad(wm_loss_fn, has_aux=True)(params["wm"])
        with jax.named_scope("optimizer"):
            updates, opt_states["wm"] = txs["wm"].update(wm_grads, opt_states["wm"], params["wm"])
            params["wm"] = optax.apply_updates(params["wm"], updates)

        # ---------------- behaviour: actor -------------------------------
        imagined_prior0 = jax.lax.stop_gradient(wm_aux["zs"]).reshape(T * B, stoch_flat)
        recurrent0 = jax.lax.stop_gradient(wm_aux["hs"]).reshape(T * B, R)
        true_continue0 = (1 - batch["terminated"]).reshape(T * B, 1)

        def rollout(actor_params, key):
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
            with jax.named_scope("imagination"):
                _, (states, actions) = jax.lax.scan(img_step, (imagined_prior0, recurrent0, a0), keys)
            trajectories = jnp.concatenate([state0[None], states], axis=0)  # [H+1, TB, L]
            imagined_actions = jnp.concatenate([a0[None], actions], axis=0)
            return trajectories, imagined_actions

        def actor_loss_fn(actor_params, moments):
            with jax.named_scope("actor"):
                trajectories, imagined_actions = rollout(actor_params, k_img)
                values = TwoHotEncodingDistribution(
                    critic_apply(params["critic"], trajectories), dims=1
                ).mean
                rewards_img = TwoHotEncodingDistribution(
                    wm_apply(params["wm"], WorldModel.reward, trajectories), dims=1
                ).mean
                continues = Independent(
                    BernoulliSafeMode(logits=wm_apply(params["wm"], WorldModel.cont, trajectories)), 1
                ).mode
                continues = jnp.concatenate([true_continue0[None], continues[1:]], axis=0)
                lv = lambda_values_op(rewards_img[1:], values[1:], continues[1:] * gamma, lmbda)
                discount = jax.lax.stop_gradient(
                    unrolled_cumprod(continues * gamma) / gamma
                )
                moments, offset, invscale = update_moments(
                    moments,
                    lv,
                    float(moments_cfg.decay),
                    float(moments_cfg.max),
                    float(moments_cfg.percentile.low),
                    float(moments_cfg.percentile.high),
                )
                baseline = values[:-1]
                normed_lv = (lv - offset) / invscale
                normed_baseline = (baseline - offset) / invscale
                advantage = normed_lv - normed_baseline
                pre_dist = actor_apply(actor_params, jax.lax.stop_gradient(trajectories))
                from .agent import actor_dists

                dists = actor_dists(actor, pre_dist)
                if is_continuous:
                    objective = advantage
                else:
                    logprobs = []
                    start = 0
                    for d, adim in zip(dists, actions_dim):
                        act = jax.lax.stop_gradient(imagined_actions[..., start : start + adim])
                        logprobs.append(d.log_prob(act)[..., None][:-1])
                        start += adim
                    objective = sum(logprobs) * jax.lax.stop_gradient(advantage)
                entropy = ent_coef * sum(d.entropy() for d in dists)[..., None]
                policy_loss = -jnp.mean(discount[:-1] * (objective + entropy[:-1]))
                aux = {
                    "trajectories": jax.lax.stop_gradient(trajectories),
                    "lambda_values": jax.lax.stop_gradient(lv),
                    "discount": discount,
                    "moments": jax.tree.map(jax.lax.stop_gradient, moments),
                }
            return policy_loss, aux

        (policy_loss, a_aux), a_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(
            params["actor"], moments
        )
        with jax.named_scope("optimizer"):
            updates, opt_states["actor"] = txs["actor"].update(a_grads, opt_states["actor"], params["actor"])
            params["actor"] = optax.apply_updates(params["actor"], updates)
        moments = a_aux["moments"]

        # ---------------- critic ------------------------------------------
        traj_sg = a_aux["trajectories"]
        lv_sg = a_aux["lambda_values"]
        discount = a_aux["discount"]

        def critic_loss_fn(critic_params):
            with jax.named_scope("critic"):
                qv = TwoHotEncodingDistribution(
                    critic_apply(critic_params, traj_sg[:-1]), dims=1
                )
                target_values = TwoHotEncodingDistribution(
                    critic_apply(params["target_critic"], traj_sg[:-1]), dims=1
                ).mean
                loss = -qv.log_prob(lv_sg) - qv.log_prob(jax.lax.stop_gradient(target_values))
                return jnp.mean(loss * discount[:-1, ..., 0])

        value_loss, c_grads = jax.value_and_grad(critic_loss_fn)(params["critic"])
        with jax.named_scope("optimizer"):
            updates, opt_states["critic"] = txs["critic"].update(c_grads, opt_states["critic"], params["critic"])
            params["critic"] = optax.apply_updates(params["critic"], updates)

        # target critic EMA (reference dreamer_v3.py:674-680)
        step = opt_states["step"] + 1
        do_t = (step % target_freq) == 0
        with jax.named_scope("critic"):
            params["target_critic"] = jax.tree.map(
                lambda t, s: jnp.where(do_t, (1 - tau) * t + tau * s, t),
                params["target_critic"],
                params["critic"],
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
            "State/kl": wm_aux["State/kl"],
            "State/post_entropy": jnp.mean(post_ent),
            "State/prior_entropy": jnp.mean(prior_ent),
            "Loss/policy_loss": policy_loss,
            "Loss/value_loss": value_loss,
        }
        return params, opt_states, moments, metrics

    acknowledge_partial_donation()  # uint8/flag leaves can't alias; expected

    out_shardings = None if state_shardings is None else (*state_shardings, None)  # metrics: the compiler's choice

    @partial(jax.jit, donate_argnums=(0, 1, 2, 3), out_shardings=out_shardings)
    def train(params, opt_states, moments, batches, keys):
        """G gradient steps in ONE device call: scan `one_step` over the
        leading axis of `batches` [G, T, B, ...] / `keys` [G] (the reference
        samples n_samples=G at dreamer_v3.py:664-671 then loops in python;
        here the loop is on device, so per-step dispatch overhead vanishes).
        Returned metrics are [G]-shaped. `batches` is donated too: the
        [G, T, B, ...] replay batch is the largest transient HBM buffer of
        the heaviest model, consumed exactly once — donating it lets XLA
        reuse that memory for activations (callers must not reuse a batch
        across calls; the prefetchers hand out fresh arrays every burst)."""

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


@partial(jax.jit, static_argnames="g")
def burst_keys(root_key: jax.Array, g: int):
    """The next root key and the ``g`` keys of one burst, the values of
    ``root_key, sub = split(root_key)`` and ``split(sub, g)``, in ONE
    dispatch. Eagerly they are three (two splits and the unstack between
    them), 2.2 ms of the learner's chain from a packet to the train step's
    start on the device, and since the ring's copies no longer hide that
    chain the act that waits behind the train step waits that much longer
    (PERF.md, PR 33)."""
    root_key, sub = jax.random.split(root_key)
    return root_key, jax.random.split(sub, g)


_PLAYER_TAG = iter(range(1 << 30))  # unique retrace-detector tags per player


def make_player(wm: WorldModel, actor: Actor, cfg: Config, actions_dim, is_continuous: bool, num_envs: int):
    """Recurrent player (replaces reference PlayerDV3, agent.py:596-693):
    state = (recurrent h, stochastic z, last action a), all [N, ...]. Runs
    wherever its params are committed (see parallel/placement.py): host CPU
    backend by default when the learner sits on an accelerator. The
    PRNG key is threaded through the jitted step so the env loop never
    dispatches a host-side `jax.random.split` per frame."""
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)

    @jax.jit
    def init_state(params, mask=None, state=None):
        h0, z0 = wm.apply(
            {"params": params["wm"]}, (num_envs,), method=WorldModel.initial_states
        )
        a0 = jnp.zeros((num_envs, int(sum(actions_dim))))
        if state is None or mask is None:
            return (h0, z0, a0)
        h, z, a = state
        m = mask[:, None]
        return (jnp.where(m, h0, h), jnp.where(m, z0, z), jnp.where(m, a0, a))

    def _step(params, obs, state, key, greedy=False, action_mask=None):
        h, z, a = state
        obs = normalize_obs(obs, cnn_keys)
        embedded = wm.apply({"params": params["wm"]}, obs, method=WorldModel.embed)
        h = wm.apply(
            {"params": params["wm"]},
            jnp.concatenate([z, a], -1),
            h,
            method=WorldModel.recurrent_step,
        )
        key, k1, k2 = jax.random.split(key, 3)
        z = wm.apply(
            {"params": params["wm"]}, h, embedded, k1, method=WorldModel.representation_step
        )
        pre = actor.apply({"params": params["actor"]}, jnp.concatenate([z, h], -1))
        acts, _ = sample_actor_actions(actor, pre, k2, greedy=greedy, mask=action_mask)
        a = jnp.concatenate(acts, -1)
        if is_continuous:
            env_actions = a
        else:
            env_actions = jnp.stack([jnp.argmax(x, axis=-1) for x in acts], axis=-1)
        return env_actions, a, (h, z, a), key

    # retrace-accounted (telemetry.xla): the overlap invariant is that the
    # pinned player step never retraces after warmup — one trace per greedy
    # variant. The tag is uniqued per make_player call so successive
    # in-process runs with different shapes don't count against each other.
    step = partial(jax.jit, static_argnames=("greedy",))(
        _xla.RETRACE_DETECTOR.wrap(_step, f"dreamer_v3.player_step#{next(_PLAYER_TAG)}")
    )
    return init_state, step


def player_params_view(player_init, player_step_fn, params, obs_space, cnn_keys, mlp_keys, num_envs: int):
    """``view(params)``: the leaves of ``params``'s ``wm`` and ``actor`` that
    the player made by :func:`make_player` reads, found by tracing every call
    the env loop makes of it (first state, masked reset, step, greedy step)
    and not by a list of module names (`placement.read_subtree`)."""
    blank = {k: np.zeros((num_envs,) + tuple(obs_space[k].shape), obs_space[k].dtype) for k in cnn_keys + mlp_keys}
    obs = prepare_obs(blank, cnn_keys, mlp_keys, num_envs)

    def probe(tree, obs, key):
        state = player_init(tree)
        reset = player_init(tree, jnp.zeros((num_envs,), bool), state)
        return reset, player_step_fn(tree, obs, state, key), player_step_fn(tree, obs, state, key, greedy=True)

    return read_subtree({"wm": params["wm"], "actor": params["actor"]}, probe, obs, jax.random.key(0))


@register_algorithm(name="dreamer_v3")
def main(dist: Distributed, cfg: Config) -> None:
    root_key = dist.seed_everything(cfg.seed)
    rank = dist.process_index
    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, rank)
    if rank == 0:
        save_configs(cfg, log_dir)

    # crash-prone suites restart in place; the loop patches the buffer via
    # patch_restarted_envs (reference dreamer_v3.py:385-399). Fleet mode
    # (algo.fleet.workers > 0): env stepping lives in supervised worker
    # PROCESSES (sheeprl_tpu/fleet/) — the learner only probes the spaces.
    if FleetEngine.configured(cfg):
        envs = None
        obs_space, action_space = probe_env_spaces(cfg, cfg.seed, rank)
    else:
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
    # multi-axis mesh (fabric.mesh.fsdp/tp > 1): world-model params flow
    # through the rule engine's inferred specs instead of replication; a
    # strict no-op on pure-dp meshes (the bit-identical 1-D path)
    params = maybe_shard_params(cfg, dist, params)

    txs, opt_states = build_optimizers(cfg, params)
    if state:
        opt_states = state["opt_states"]
    moments = dist.replicate(state["moments"] if state else init_moments())
    opt_states = maybe_shard_opt_state(cfg, dist, opt_states)

    seq_len = int(cfg.algo.per_rank_sequence_length)
    buffer_size = int(cfg.buffer.size) if not cfg.dry_run else max(4 * seq_len, 64)
    rb = EnvIndependentReplayBuffer(
        buffer_size,
        n_envs=num_envs,
        obs_keys=obs_keys,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}") if cfg.buffer.memmap else None,
        buffer_cls=SequentialReplayBuffer,
        seed=cfg.seed + 1024 * rank,
    )
    if state and cfg.buffer.checkpoint and "rb" in state:
        rb.load_state_dict(state["rb"])

    player_init, player_step_fn = make_player(wm, actor, cfg, actions_dim, is_continuous, num_envs)
    # Actor/learner split (parallel/placement.py): per-step inference runs on
    # the player device, which `auto` picks by the bytes the player reads; the
    # mirror holds those leaves of {wm, actor} and no others (the decoder and
    # the reward and continue heads enter none of the player's programs) and
    # re-syncs them after every train burst — the only place params change.
    player_view = player_params_view(player_init, player_step_fn, params, obs_space, cnn_keys, mlp_keys, num_envs)
    mirror, pdev, player_key, root_key = make_param_mirror(cfg, dist.local_device, player_view(params), root_key)

    telem = Telemetry.setup(cfg, log_dir, rank, logger=logger, aggregator_keys=AGGREGATOR_KEYS)
    aggregator = telem.aggregator
    telem.emit(mirror.placement)
    train = make_train_fn(
        wm, actor, critic, txs, cfg, is_continuous, actions_dim,
        state_shardings=jax.tree.map(lambda x: x.sharding, (params, opt_states, moments)),
        emit=telem.emit,
    )
    # the mesh layout is a telemetry artifact: every inferred spec (and the
    # per-chip bytes accounting) lands in the JSONL stream as `sharding`
    # events — doctor's replicated_giant reads them
    for _rep in dist.take_sharding_reports():
        for _ev in _rep.events():
            telem.emit(_ev)  # lint: ok[hot-loop-emit] one-time setup loop (sharding reports), not the step loop
    ckpt = CheckpointManager(log_dir, keep_last=cfg.checkpoint.keep_last, enabled=rank == 0)
    guard = RunGuard.setup(cfg, ckpt, telem, log_dir)
    ckpt = guard.ckpt
    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if state and "ratio" in state:
        ratio.load_state_dict(state["ratio"])

    # batches shard over the DATA axes only (dp × fsdp): under tensor
    # parallelism the tp replicas see the same batch, so the global batch
    # does not scale with tp (== world_size on every non-tp mesh)
    batch_size = int(cfg.algo.per_rank_batch_size) * dist.data_parallel_size
    total_steps = int(cfg.algo.total_steps) if not cfg.dry_run else 4 * num_envs
    learning_starts = int(cfg.algo.learning_starts) if not cfg.dry_run else 0
    policy_step = state["policy_step"] if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0
    clip_rewards_fn = (lambda r: np.tanh(r)) if cfg.env.clip_rewards else (lambda r: r)

    # [G, T, B, ...] replay batches: HBM-resident ring (rows are shipped
    # once, batches gather on device) on a single accelerator, else
    # host-sampled + dp-sharded staging (data/device_ring.py)
    prefetch = make_sequential_prefetcher(
        cfg,
        dist,
        rb,
        batch_size,
        seq_len,
        cnn_keys=cnn_keys,
        row_bytes_hint=estimate_row_bytes(obs_space, act_total),
        emit=telem.emit,
    )
    pending_metrics: list = []

    if envs is not None:
        obs, _ = envs.reset(seed=cfg.seed)
        player_state = player_init(mirror.params)

        # row 0: reset obs, zero action/reward, is_first=1 (reference :536-549)
        step_data: Dict[str, np.ndarray] = {}
        for k in obs_keys:
            step_data[k] = np.asarray(obs[k])[np.newaxis]
        step_data["actions"] = np.zeros((1, num_envs, act_total), np.float32)
        step_data["rewards"] = np.zeros((1, num_envs, 1), np.float32)
        step_data["terminated"] = np.zeros((1, num_envs, 1), np.float32)
        step_data["truncated"] = np.zeros((1, num_envs, 1), np.float32)
        step_data["is_first"] = np.ones((1, num_envs, 1), np.float32)

    def _ckpt_state() -> Dict[str, Any]:
        s: Dict[str, Any] = {
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

    # SHEEPRL_TPU_PROGRESS=N: wall-clock trace every N policy steps (stderr)
    _progress = int(os.environ.get("SHEEPRL_TPU_PROGRESS", "0") or 0)
    _t0 = time.perf_counter()

    p_step = policy_step  # player-side env-step counter (== policy_step with the inline source)
    act_call: Dict[str, int] = {}  # `Player/dispatch`'s counts, the same for every act: counted at the first

    def interact(sink) -> None:
        """ONE vector env step (the reference train() env block): act from
        the mirror snapshot and record the replay-row mutations into `sink`,
        a `RecordingSink` that rides a packet and is applied learner-side in
        order."""
        nonlocal obs, player_state, player_key, p_step
        if p_step <= learning_starts:
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
            with telem.span("Player/act"):
                with telem.span("Player/prepare"):
                    host_obs = prepare_obs(obs, cnn_keys, mlp_keys, num_envs)
                    masks = extract_masks(obs, num_envs)
                    if not act_call:
                        act_call.update(call_counts(mirror.params, host_obs, player_state, player_key, masks))
                with telem.span("Player/dispatch", leaves=act_call["leaves"], h2d_bytes=act_call["h2d_bytes"]):
                    env_actions, actions_cat, player_state, player_key = player_step_fn(
                        mirror.current(), host_obs, player_state, player_key, action_mask=masks
                    )
                with telem.span("Player/fetch", arrays=2, d2h_bytes=actions_cat.nbytes + env_actions.nbytes):
                    actions_np = np.asarray(actions_cat)
                    actions_env = np.asarray(env_actions)
            if is_continuous:
                actions_env = actions_env.reshape(num_envs, -1)
            elif not is_multidiscrete:
                actions_env = actions_env.reshape(num_envs)

        with telem.span("Player/record"):
            step_data["actions"] = actions_np.reshape(1, num_envs, -1)
            sink.add(step_data, validate_args=cfg.buffer.validate_args)

        with telem.span("Player/env_step"):
            next_obs, rewards, terminated, truncated, info = envs.step(actions_env)
        p_step += num_envs
        with telem.span("Player/record"):
            record_step(sink, next_obs, rewards, terminated, truncated, info)

    def record_step(sink, next_obs, rewards, terminated, truncated, info) -> None:
        """The rows one vector env step leaves behind, and the reset
        bookkeeping of the envs that ended in it."""
        nonlocal obs, player_state
        dones = np.logical_or(terminated, truncated)

        for ep_rew, ep_len in episode_stats(info):
            # through the sink: the aggregator is not thread-safe, so these
            # ride the packet and land on the learner thread
            sink.stat("Rewards/rew_avg", ep_rew)
            sink.stat("Game/ep_len_avg", ep_len)

        # real next obs (final obs for done envs)
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
        restarted = patch_restarted_envs(info, dones, sink, step_data)
        if restarted is not None:
            player_state = player_init(mirror.current(), restarted, player_state)

        dones_idxes = np.nonzero(dones)[0].tolist()
        if dones_idxes:
            # closing row for finished episodes (reference :639-657)
            reset_data: Dict[str, np.ndarray] = {}
            for k in obs_keys:
                reset_data[k] = real_next_obs[k][dones_idxes][np.newaxis]
            reset_data["terminated"] = step_data["terminated"][:, dones_idxes]
            reset_data["truncated"] = step_data["truncated"][:, dones_idxes]
            reset_data["actions"] = np.zeros((1, len(dones_idxes), act_total), np.float32)
            reset_data["rewards"] = step_data["rewards"][:, dones_idxes]
            reset_data["is_first"] = np.zeros_like(reset_data["terminated"])
            sink.add(reset_data, dones_idxes, validate_args=cfg.buffer.validate_args)
            # open row for the new episodes
            step_data["rewards"][:, dones_idxes] = 0
            step_data["terminated"][:, dones_idxes] = 0
            step_data["truncated"][:, dones_idxes] = 0
            step_data["is_first"][:, dones_idxes] = 1
            mask = np.zeros((num_envs,), bool)
            mask[dones_idxes] = True
            player_state = player_init(mirror.current(), mask, player_state)

        obs = next_obs

    def flush_logs() -> None:
        nonlocal last_log
        if policy_step - last_log >= cfg.metric.log_every or cfg.dry_run:
            with telem.span("Time/log_flush"):
                for m in pending_metrics:  # host-sync deferred to log cadence
                    for k, v in m.items():
                        aggregator.update(k, np.asarray(v))
                pending_metrics.clear()
                telem.log(policy_step)
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
    # (the protocol is in engine/overlap.py): supervised worker PROCESSES
    # running the recurrent player against published {wm, actor} snapshots,
    # each worker's ops replayed against its own global env columns of the
    # per-env sequential buffer (apply_sliced: a quarantined slice simply
    # stops growing); else `play` on a player thread beside this one, or
    # inline on this thread (`algo.overlap.enabled`).
    source = FleetEngine.setup(cfg, telem, guard, total_steps=total_steps, initial_step=policy_step)
    if source.enabled:
        source.start(
            "sheeprl_tpu.fleet.programs:dreamer_v3_program", num_envs, cfg, apply=FleetEngine.apply_sliced
        )
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
        if _progress and policy_step % _progress < taken:
            print(
                f"[progress] step={policy_step} t={time.perf_counter() - _t0:.1f}s",
                file=sys.stderr,
                flush=True,
            )
        # one train call per owed burst, always [G, ...] shapes (no new
        # compiled shapes, no retraces); dispatch is async, so staging the
        # next burst overlaps device execution
        bursting = False
        for i, g in enumerate(gs):
            if g <= 0:
                continue
            with telem.span("Time/train_time", grad_steps=g, burst=source.burst):
                bursting = True
                batches = prefetch.take(g)  # [G, T, B, ...]
                root_key, keys = burst_keys(root_key, g)
                params, opt_states, moments, metrics = train(params, opt_states, moments, batches, keys)
            # held on device until log time; not at all when metrics are off
            if not MetricAggregator.disabled:
                pending_metrics.append(metrics)
            nxt = next((x for x in gs[i + 1 :] if x > 0), 0)
            if nxt > 0:
                with telem.span("Time/replay_stage"):
                    prefetch.stage(nxt)
        if bursting:
            mirror.refresh(player_view(params))
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
        test_cfg = Config({**cfg.to_dict(), "env": {**cfg.env.to_dict(), "num_envs": 1}})
        test_env = vectorize(test_cfg, cfg.seed, rank, log_dir).envs[0]
        t_init, t_step = make_player(wm, actor, cfg, actions_dim, is_continuous, 1)
        t_params = jax.device_put({"wm": params["wm"], "actor": params["actor"]}, pdev)
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
                "actor": params["actor"],
                "critic": params["critic"],
                "target_critic": params["target_critic"],
                "moments": moments,
            },
            log_dir,
        )
    if logger is not None:
        logger.close()


@register_evaluation(algorithms="dreamer_v3")
def evaluate_dreamer_v3(dist: Distributed, cfg: Config, state: Dict[str, Any]) -> None:
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
    t_init, t_step = make_player(wm, actor, cfg, actions_dim, is_continuous, 1)
    pdev = player_device(cfg, dist.local_device)
    t_params = jax.device_put({"wm": params["wm"], "actor": params["actor"]}, pdev)
    t_state = t_init(t_params)

    def _step(o, s, k, greedy, mask=None):
        env_actions, _, s, k = t_step(t_params, o, s, k, greedy, action_mask=mask)
        return env_actions, s, k

    test(_step, t_state, env, cfg, log_dir, logger, device=pdev)
