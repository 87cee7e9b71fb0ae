"""Recurrent PPO with a sequence model as its backbone (`algo.backbone.name`
other than `lstm`; `models/sequence.py`, howto/sequence_policy.md).

The run around the loop (seeding, envs, resume, telemetry, checkpoints, the
cadence after an update, the close) is `loop.LoopRun`, shared with the LSTM
loop in `ppo_recurrent.py`. What differs from it, and why acting, recording
and the update are written again here: the observation is a token id that goes through the embedding and
the action an id of the held vocabulary slice, so neither `prev_actions` nor a
one-hot of the action exists anywhere (at 16384 ids a `[T, B]` one-hot would
be 1 GB); the recurrent carry is the per-env latent cache, donated to and
updated in place by one jitted decode step for all envs, which also keeps the
log-probabilities and values of the rollout on the device; the update takes
whole `[rollout_steps, b]` sequences with a causal same-episode mask.

`main` drives this module's one-token policy, or a block-diffusion policy
(`block_diffusion_policy.py`, a backbone that names a `block_length`) through
the same names: `new_state`, `make_act_fn`, `make_value_fn`, `make_update_fn`
and `STORED`, the rollout's arrays the decode step keeps. An env step is then
a block of ids, observed and acted.

The context never crosses a rollout boundary: the policy sees `is_first` at
the first step of every rollout (the cache restarts, `Time/cache_reset`), and
an episode still running at the last step counts as truncated there, its
reward bootstrapped with the value of the next token. `algo.rollout_steps` is
the cache's capacity (in blocks for a block-diffusion policy).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ...config import Config, instantiate
from ...models import sequence as seq
from ...ops import gae as gae_op
from ...optim import clipped
from ...parallel import Distributed
from ...parallel.placement import make_param_mirror, tree_bytes
from ...telemetry.spans import call_counts
from ...utils.env import episode_stats
from ..ppo.loss import entropy_loss, policy_loss, value_loss
from .agent import BlockDiffusionPolicy, SequencePolicy, build_agent
from .loop import LoopRun
from .utils import update_coefs


STORED = ("logprobs", "values")  # what the decode step keeps of a rollout, `[T, envs, ...]`, for the update


def new_state(module: SequencePolicy, num_envs: int, capacity: int) -> Dict[str, Any]:
    """What the decode step carries: the latent cache, and the rollout's log-probabilities and values `[capacity, envs]`."""
    return {"cache": seq.new_cache(module.cfg, num_envs, capacity), "logprobs": jnp.zeros((capacity, num_envs)),
            "values": jnp.zeros((capacity, num_envs))}


def make_act_fn(module: SequencePolicy):
    @partial(jax.jit, donate_argnums=(1,))
    def act(params, state, tokens, is_first, key):
        t = state["cache"]["pos"]
        logits, values, cache = seq.forward_decode(params, state["cache"], tokens, is_first, module.cfg)
        with jax.named_scope("head"):
            actions = jax.random.categorical(jax.random.fold_in(key, t), logits)
            logprobs = jnp.take_along_axis(jax.nn.log_softmax(logits), actions[:, None], 1)[:, 0]
        return actions, {"cache": cache, "logprobs": state["logprobs"].at[t].set(logprobs), "values": state["values"].at[t].set(values)}

    return act


def make_value_fn(module: SequencePolicy):
    @jax.jit
    def value_fn(params, state, tokens):
        return seq.forward_decode(params, state["cache"], tokens, jnp.zeros(tokens.shape, bool), module.cfg, write=False)[1]

    return value_fn


@partial(jax.jit, donate_argnums=(0,))
def restart(state):
    """The rollout-boundary restart: the next token is written at row 0 and read alone; the rows stay, unread."""
    cache = state["cache"]
    return {**state, "cache": {**cache, "pos": jnp.zeros_like(cache["pos"]), "start": jnp.zeros_like(cache["start"])}}


def ppo_losses(new_logprobs: jax.Array, entropy: jax.Array, new_values: jax.Array, mb: Dict[str, jax.Array], coefs: Dict[str, jax.Array],
               cfg: Config) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The clipped policy loss, the value loss and the entropy loss of one minibatch of whole sequences, and their sum
    by the coefficients: (loss, the three by name)."""
    advantages = mb["advantages"]
    if bool(cfg.algo.normalize_advantages):
        advantages = (advantages - jnp.mean(advantages)) / (jnp.std(advantages) + 1e-8)
    reduction = str(cfg.algo.loss_reduction)
    pg_loss = policy_loss(new_logprobs, mb["logprobs"], advantages, coefs["clip_coef"], reduction)
    v_loss = value_loss(new_values, mb["values"], mb["returns"], coefs["clip_coef"], bool(cfg.algo.clip_vloss), reduction)
    ent_loss = entropy_loss(entropy, reduction)
    loss = pg_loss + coefs["vf_coef"] * v_loss + coefs["ent_coef"] * ent_loss
    return loss, {"Loss/policy_loss": pg_loss, "Loss/value_loss": v_loss, "Loss/entropy_loss": ent_loss}


def minibatch_update(loss_fn: Callable, tx, cfg: Config, num_minibatches: int, mb_size: int):
    """Epochs x minibatches of whole sequences as one jitted program, each step `jax.value_and_grad(loss_fn)` and the
    optimizer. `data` is sequence-major `[envs, T, ...]`. Returns the losses' means beside, per gradient step, the
    losses and the expert layers' load (`loss_fn`'s aux: (losses, load))."""
    update_epochs = int(cfg.algo.update_epochs)

    @partial(jax.jit, donate_argnums=(0, 1))
    def update(params, opt_state, data: Dict[str, jax.Array], coefs, key):
        num_sequences = data["tokens"].shape[0]

        def epoch_step(carry, _):
            params, opt_state, key = carry
            key, pk = jax.random.split(key)
            idxs = jax.random.permutation(pk, num_sequences)[: num_minibatches * mb_size].reshape(num_minibatches, mb_size)

            def mb_step(carry2, idx):
                params, opt_state = carry2
                mb = jax.tree.map(lambda x: jnp.take(x, idx, axis=0), data)
                (_, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, mb, coefs)
                with jax.named_scope("optimizer"):
                    updates, opt_state = tx.update(grads, opt_state, params)
                    params = optax.apply_updates(params, jax.tree.map(lambda u: u * coefs["lr_frac"], updates))
                return (params, opt_state), aux

            (params, opt_state), auxs = jax.lax.scan(mb_step, (params, opt_state), idxs)
            return (params, opt_state, key), auxs

        (params, opt_state, key), (losses, load) = jax.lax.scan(epoch_step, (params, opt_state, key), None, length=update_epochs)
        return params, opt_state, jax.tree.map(jnp.mean, losses), {"losses": losses, "load": load}

    return update


def make_update_fn(module: SequencePolicy, tx, cfg: Config, num_minibatches: int, mb_size: int):
    """The update of a one-token policy: one causal same-episode forward over each minibatch's `[b, T]` sequences."""

    def loss_fn(params, mb: Dict[str, jax.Array], coefs: Dict[str, jax.Array]):
        logits, new_values, load = seq.forward_train(params, mb["tokens"], mb["is_first"], module.cfg)
        with jax.named_scope("loss"):
            logp = jax.nn.log_softmax(logits)
            new_logprobs = jnp.take_along_axis(logp, mb["actions"][..., None], -1)[..., 0]
            entropy = -jnp.sum(jnp.exp(logp) * logp, -1)
            loss, losses = ppo_losses(new_logprobs, entropy, new_values, mb, coefs, cfg)
        return loss, (losses, load)

    return minibatch_update(loss_fn, tx, cfg, num_minibatches, mb_size)


def moe_load_event(load: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """The train call's `moe_load` event from what the update returned per gradient step (summed over the expert
    layers; `max_over_mean` the worst layer's; `fallbacks` the layers and steps whose load took the worst case's
    buffer)."""
    routed, rows = int(np.sum(load["routed_here"])), int(np.sum(load["rows"]))
    return {"event": "moe_load", "routed_here": routed, "rows": rows, "slot_occupancy": routed / rows,
            "max_over_mean": float(np.max(load["max_over_mean"])), "dropped": int(np.sum(load["dropped"])),
            "fallbacks": int(np.sum(load["fallbacks"]))}


def main(dist: Distributed, cfg: Config) -> None:
    if dist.world_size != 1:
        raise ValueError("the sequence backbone runs on one device: its share of a layer is one chip's (fabric.devices=1)")
    run = LoopRun(dist, cfg)
    envs, state = run.envs, run.state
    num_envs = int(cfg.env.num_envs)
    root_key, init_key = jax.random.split(run.root_key)
    module, params = build_agent(dist, cfg, envs.single_observation_space, envs.single_action_space, init_key,
                                 state["params"] if state else None)
    blocks = isinstance(module, BlockDiffusionPolicy)  # a block of ids an env step, committed over a few forwards
    if blocks:
        from . import block_diffusion_policy as policy
    else:
        from . import sequence_policy as policy
    L, S = module.block_length, module.denoising_steps
    tx = clipped(instantiate(cfg.algo.optimizer), cfg.algo.get("max_grad_norm", 0.0))
    opt_state = dist.replicate(state["opt_state"] if state else tx.init(params))

    rollout_steps = int(cfg.algo.rollout_steps)
    if int(cfg.algo.per_rank_sequence_length) != rollout_steps:
        raise ValueError("a sequence policy trains on whole rollouts: algo.per_rank_sequence_length must equal algo.rollout_steps")
    num_batches = int(cfg.algo.per_rank_num_batches)
    mb_size = max(num_envs // num_batches, 1) if num_batches > 0 else 1
    num_minibatches = num_envs // mb_size
    grad_steps = num_minibatches * int(cfg.algo.update_epochs)

    act = policy.make_act_fn(module)
    value_fn = policy.make_value_fn(module)
    update = policy.make_update_fn(module, tx, cfg, num_minibatches, mb_size)
    gae_fn = jax.jit(partial(gae_op, num_steps=rollout_steps, gamma=cfg.algo.gamma, gae_lambda=cfg.algo.gae_lambda))

    policy_steps_per_iter = num_envs * rollout_steps
    run.begin(policy_steps_per_iter)
    telem = run.telem
    aggregator = telem.aggregator

    # acting and the update are serial on one in-order stream: on the learner's device the mirror holds the
    # learner's own arrays (`in_order`), so no second copy of the parameters exists
    mirror, pdev, player_key, root_key = make_param_mirror(cfg, dist.local_device, params, root_key, allow_async=False, in_order=True)
    telem.emit(mirror.placement)
    carry = jax.device_put(policy.new_state(module, num_envs, rollout_steps), pdev)
    scfg = module.cfg
    telem.emit({
        "event": "sequence_policy", "backbone": str(cfg.algo.backbone.name), "layers": scfg.num_hidden_layers,
        "experts_held": scfg.experts_held, "first_expert": scfg.first_expert, "n_routed_experts": scfg.n_routed_experts,
        "heads_held": scfg.heads_held, "num_attention_heads": scfg.num_attention_heads, "vocab_held": scfg.vocab_held,
        "vocab_size": scfg.vocab_size, "cache_bytes": tree_bytes(carry["cache"]), "param_bytes": tree_bytes(params),
        "cache_layout": {k: list(x.format.layout.major_to_minor) for k, x in carry["cache"].items() if x.ndim > 1},
    })
    if blocks:
        telem.emit(policy.block_event(module, num_envs, rollout_steps, mb_size))

    obs, _ = envs.reset(seed=cfg.seed)
    token_shape = (L,) if blocks else ()

    def learner():
        return params, opt_state, root_key

    act_call: Dict[str, int] = {}  # `Player/dispatch`'s counts, the same for every act: counted at the first

    def tokens_of(o: Dict[str, np.ndarray]) -> np.ndarray:
        return np.asarray(o[module.token_key], np.int32).reshape(num_envs, *token_shape)

    for update_iter in range(run.start_iter, run.num_updates + 1):
        telem.tick(run.policy_step)
        with telem.span("Time/cache_reset", rows=num_envs * rollout_steps * L):
            carry = restart(carry)
        rows: Dict[str, list] = {k: [] for k in ("tokens", "actions", "rewards", "dones", "is_first")}
        is_first = np.ones(num_envs, bool)  # the context never crosses a rollout boundary
        player_key, rollout_key = jax.random.split(player_key)
        with telem.span("Time/env_interaction_time", env_steps=policy_steps_per_iter):
            for t in range(rollout_steps):
                with telem.span("Player/act", tokens=num_envs * L, cache_rows=num_envs * L * (t + 1), forwards=S):
                    with telem.span("Player/prepare"):
                        tokens = tokens_of(obs)
                        if not act_call:
                            act_call.update(call_counts(mirror.params, carry, tokens, is_first, rollout_key))
                    with telem.span("Player/dispatch", leaves=act_call["leaves"], h2d_bytes=act_call["h2d_bytes"]):
                        # lint: ok[rng-reuse] `call_counts` counts the key as a leaf and does not consume it
                        actions, carry = act(mirror.current(), carry, tokens, is_first, rollout_key)
                    with telem.span("Player/fetch", arrays=1, d2h_bytes=actions.nbytes):
                        np_actions = np.asarray(actions)
                with telem.span("Player/env_step"):
                    obs, rewards, terminated, truncated, info = envs.step(np_actions)
                run.policy_step += num_envs
                with telem.span("Player/record"):
                    rewards = np.asarray(rewards, np.float32)
                    dones = np.logical_or(terminated, truncated)
                    cut = np.array(truncated, bool)
                    final = tokens_of(obs)
                    if np.any(cut) and "final_obs" in info:
                        final[cut] = [np.asarray(info["final_obs"][i][module.token_key]).reshape(token_shape) for i in np.nonzero(cut)[0]]
                    if t == rollout_steps - 1:  # an episode still running is truncated where the context is cut
                        cut |= ~dones
                        dones = np.ones(num_envs, bool)
                    if np.any(cut):
                        with telem.span("Player/bootstrap", envs=int(np.sum(cut))):
                            rewards[cut] += float(cfg.algo.gamma) * np.asarray(value_fn(mirror.current(), carry, final))[cut]
                    for k, v in (("tokens", tokens), ("actions", np_actions.astype(np.int32)), ("rewards", rewards),
                                 ("dones", dones.astype(np.float32)), ("is_first", is_first)):
                        rows[k].append(v)
                    is_first = dones
                    for ep_rew, ep_len in episode_stats(info):
                        aggregator.update("Rewards/rew_avg", ep_rew)
                        aggregator.update("Game/ep_len_avg", ep_len)

        with telem.span("Time/train_time", grad_steps=grad_steps, burst=update_iter, tokens=policy_steps_per_iter * L):
            host = {k: np.stack(v) for k, v in rows.items()}  # [T, envs, ...]
            returns, advantages = gae_fn(host["rewards"], carry["values"], host["dones"], jnp.zeros((num_envs,)))
            data = {k: jax.device_put(np.swapaxes(host[k], 0, 1), dist.batch_sharding) for k in host}
            data.update({k: jax.device_put(jnp.swapaxes(v, 0, 1), dist.batch_sharding) for k, v in
                         [(k, carry[k]) for k in policy.STORED] + [("returns", returns), ("advantages", advantages)]})
            coefs = update_coefs(cfg, update_iter, run.num_updates)
            root_key, up_key = jax.random.split(root_key)
            params, opt_state, metrics, report = update(params, opt_state, data, coefs, up_key)
            telem.record_grad_steps(grad_steps)
            mirror.refresh(params)  # blocking: the next rollout acts with these

        # the update's losses and load, the log and the checkpoint: the host's work between the update and the next rollout
        with telem.span("Time/update_report"):
            metrics, load = jax.device_get((metrics, report["load"]))  # host-sync: ok (update cadence)
            for k, v in metrics.items():
                aggregator.update(k, v)
            if np.sum(load["rows"]) > 0:
                telem.emit(moe_load_event(load))
            stop = run.end_iteration(update_iter, learner)
        if stop:
            break

    run.close(learner)
