"""Acting and the update of a block-diffusion policy (`models/block_diffusion.py`)
on the recurrent on-policy loop of `sequence_policy.main`, which drives it as it
drives a one-token policy (howto/block_diffusion_policy.md).

An env step hands the policy an observed block of `L` ids for every env and
takes a block of `L` ids back, committed over `S` denoising forwards in ONE
jitted call for all envs:

* forward 1 runs over the observed block and a fully masked block, `2 L` rows
  an env: it writes the observed block's keys and values into the cache,
  gives the value from the observed block's last position, and the logits of
  step 0 at the masked positions;
* forward `s` > 1 runs over the partly filled block, `L` rows an env, through
  the cache; nothing is written;
* at step `s` the positions whose `order` is `s` are committed: a uniform
  random order of the `L` positions, drawn from the act's key and independent
  of the logits, `L / S` positions a step; each committed id is sampled from
  that step's logits.

The log-probability of the block is the sum over the steps of the committed
positions' log-probabilities: the choice of positions does not depend on the
parameters, so it cancels in PPO's ratio. The rollout keeps `order` beside
the log-probabilities and values; the update rebuilds every copy from it
(copy `s` holds the ids with `order < s`) and takes each position's
log-probability from the copy that committed it.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ...config import Config
from ...models import block_diffusion as bd
from .agent import BlockDiffusionPolicy
from .sequence_policy import minibatch_update, ppo_losses

STORED = ("logprobs", "values", "order")  # what the decode step keeps of a rollout, `[T, envs, ...]`, for the update


def new_state(module: BlockDiffusionPolicy, num_envs: int, blocks: int) -> Dict[str, Any]:
    """What the decode step carries: the K/V cache of `blocks` blocks, and per env step the block's log-probability,
    the value and the order its positions were committed in."""
    return {"cache": bd.new_cache(module.cfg, num_envs, blocks * module.block_length), "logprobs": jnp.zeros((blocks, num_envs)),
            "values": jnp.zeros((blocks, num_envs)), "order": jnp.zeros((blocks, num_envs, module.block_length), jnp.int32)}


def make_act_fn(module: BlockDiffusionPolicy):
    cfg, L, S, mask_id = module.cfg, module.block_length, module.denoising_steps, module.mask_id
    ones = jnp.ones((L, L), bool)

    @partial(jax.jit, donate_argnums=(1,))
    def act(params, state, tokens, is_first, key):
        """`tokens` [N, L], the observed blocks; returns (the action blocks [N, L], the state one block on)."""
        cache = state["cache"]
        base = cache["pos"]
        t = base // L
        start = jnp.where(is_first, base, cache["start"])
        order_key, *step_keys = jax.random.split(jax.random.fold_in(key, t), S + 1)
        rank = jnp.argsort(jnp.argsort(jax.random.uniform(order_key, tokens.shape), -1), -1)
        order = rank // (L // S)
        block = jnp.full(tokens.shape, mask_id, jnp.int32)
        # forward 1: the observed block sees itself, the masked block the observed one and itself
        own = jnp.block([[ones, jnp.zeros((L, L), bool)], [ones, bd.copy_mask(L)]])
        logits, values, cache = bd.forward_decode(params, cache, jnp.concatenate([tokens, block], 1), base + jnp.arange(2 * L), own, base,
                                                  start, cfg, mask_id, write=L)
        value, logits, logprob = values[:, L - 1], logits[:, L:], jnp.zeros(tokens.shape[:1])
        for s in range(S):
            if s:
                logits = bd.forward_decode(params, cache, block, base + L + jnp.arange(L), bd.copy_mask(L), base + L, start, cfg, mask_id)[0]
            with jax.named_scope("head"):
                ids = jax.random.categorical(step_keys[s], logits)
                logp = jnp.take_along_axis(jax.nn.log_softmax(logits), ids[..., None], -1)[..., 0]
                now = order == s
                block = jnp.where(now, ids, block)
                logprob = logprob + jnp.sum(jnp.where(now, logp, 0.0), -1)
        return block, {"cache": {**cache, "pos": base + L, "start": start}, "logprobs": state["logprobs"].at[t].set(logprob),
                       "values": state["values"].at[t].set(value), "order": state["order"].at[t].set(order)}

    return act


def make_value_fn(module: BlockDiffusionPolicy):
    cfg, L = module.cfg, module.block_length

    @jax.jit
    def value_fn(params, state, tokens):
        """The value of a block that is not part of the rollout (a truncation's bootstrap): read through the cache, written nowhere."""
        cache = state["cache"]
        base = cache["pos"]
        out = bd.forward_decode(params, cache, tokens, base + jnp.arange(L), jnp.ones((L, L), bool), base, cache["start"], cfg, module.mask_id)
        return out[1][:, L - 1]

    return value_fn


def make_update_fn(module: BlockDiffusionPolicy, tx, cfg: Config, num_minibatches: int, mb_size: int, attention: str = "auto"):
    """The update: for each minibatch's `[b, T]` blocks one forward over the clean sequence and the `S` copies of every
    action block; a block's log-probability and entropy are the sums over its positions, each from its copy. The
    attention's form (`bd.resolve_train_attention`) is resolved here, once."""
    attention = bd.resolve_train_attention(attention)

    def loss_fn(params, mb: Dict[str, jax.Array], coefs: Dict[str, jax.Array]):
        logits, new_values, load = bd.forward_train(params, mb["tokens"], mb["is_first"], mb["actions"], mb["order"], module.cfg,
                                                    module.denoising_steps, module.mask_id, attention=attention)
        with jax.named_scope("loss"):
            logp = jax.nn.log_softmax(logits)
            new_logprobs = jnp.sum(jnp.take_along_axis(logp, mb["actions"][..., None], -1)[..., 0], -1)
            entropy = -jnp.sum(jnp.exp(logp) * logp, (-2, -1))
            loss, losses = ppo_losses(new_logprobs, entropy, new_values, mb, coefs, cfg)
        return loss, (losses, load)

    return minibatch_update(loss_fn, tx, cfg, num_minibatches, mb_size)


def block_event(module: BlockDiffusionPolicy, num_envs: int, blocks: int, mb_size: int) -> Dict[str, Any]:
    """The run's `block_diffusion` event: the block, the forwards an env step takes, the ids a forward commits for
    all envs, the rows one gradient step's forward carries, the mask id, the update's attention form and, for the
    kernel, the share of its tiles it computes."""
    L, S = module.block_length, module.denoising_steps
    attention = bd.resolve_train_attention()
    event = {"event": "block_diffusion", "block_length": L, "denoising_steps": S, "tokens_per_forward": num_envs * L // S,
             "update_tokens": mb_size * blocks * L * (1 + S), "mask_id": module.mask_id, "train_attention": attention}
    if attention == "kernel":
        event["attn_blocks_computed"] = bd.attn_blocks_computed(blocks, L, S)
    return event
