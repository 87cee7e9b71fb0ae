"""Recurrent PPO agent (reference sheeprl/algos/ppo_recurrent/agent.py, 470 LoC).

TPU-native re-design: the reference packs variable-length episode sequences
through a cuDNN LSTM (`pack_padded_sequence`, agent.py:67-81) — dynamic
shapes that XLA cannot tile. Here the LSTM is a `nn.scan`-lifted cell over
**fixed-length** sequences with an `is_first` reset mask applied inside the
scan: episode boundaries zero the carry exactly where the reference would
have split the batch into separate padded sequences, so the math matches
while every shape stays static.

Layout convention: sequences are time-major [L, B, ...] like the reference
(`batch_first=False`, agent.py:42). The same module serves training (L>1)
and the rollout player (L=1) — flax broadcasts one param set through the
scan, so there is no player/trainer duality.
"""
from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import flax.linen as nn
import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np

from ...models import MLP
from ...models import sequence as seq
from ..ppo.agent import PPOEncoder, actions_and_log_probs  # noqa: F401 — shared sampling


class ResetLSTMCell(nn.Module):
    """LSTM cell that zeroes its carry where `is_first` is set (reference
    `reset_recurrent_state_on_done`, ppo_recurrent.py:371-374 — done there on
    the host between steps; here inside the scan)."""

    hidden_size: int

    @nn.compact
    def __call__(self, carry, xs):
        x, is_first = xs
        c, h = carry
        c = (1.0 - is_first) * c
        h = (1.0 - is_first) * h
        (c, h), y = nn.OptimizedLSTMCell(self.hidden_size, name="lstm")((c, h), x)
        return (c, h), y


class RecurrentPPOAgent(nn.Module):
    """Encoder → [pre-MLP] → LSTM scan → [post-MLP] → actor heads + critic
    (reference RecurrentPPOAgent, agent.py:86-262).

    `__call__` consumes time-major sequences and returns
    (actor_out, values, (c, h)); `actor_out` is per-dim logits or
    [mean, log_std] like the non-recurrent PPO agent."""

    actions_dim: Sequence[int]
    is_continuous: bool
    cnn_keys: Sequence[str] = ()
    mlp_keys: Sequence[str] = ()
    cnn_features_dim: int = 512
    mlp_features_dim: int = 64
    encoder_dense_units: int = 64
    encoder_mlp_layers: int = 1
    dense_act: str = "relu"
    layer_norm: bool = True
    lstm_hidden_size: int = 64
    pre_rnn_apply: bool = False
    pre_rnn_dense_units: int = 64
    pre_rnn_layer_norm: bool = True
    post_rnn_apply: bool = False
    post_rnn_dense_units: int = 64
    post_rnn_layer_norm: bool = True
    actor_dense_units: int = 64
    actor_mlp_layers: int = 1
    actor_layer_norm: bool = True
    critic_dense_units: int = 64
    critic_mlp_layers: int = 1
    critic_layer_norm: bool = True

    @nn.compact
    def __call__(
        self,
        obs: Dict[str, jax.Array],  # values [L, B, ...]
        prev_actions: jax.Array,  # [L, B, A]
        is_first: jax.Array,  # [L, B, 1]
        carry: Tuple[jax.Array, jax.Array],  # (c, h) each [B, H]
    ):
        feat = PPOEncoder(
            cnn_keys=self.cnn_keys,
            mlp_keys=self.mlp_keys,
            cnn_features_dim=self.cnn_features_dim,
            mlp_features_dim=self.mlp_features_dim,
            dense_units=self.encoder_dense_units,
            mlp_layers=self.encoder_mlp_layers,
            dense_act=self.dense_act,
            layer_norm=self.layer_norm,
            name="feature_extractor",
        )(obs)
        x = jnp.concatenate([feat, prev_actions], axis=-1)
        if self.pre_rnn_apply:
            x = MLP(
                hidden_sizes=(self.pre_rnn_dense_units,),
                activation=self.dense_act,
                norm_layer="layernorm" if self.pre_rnn_layer_norm else None,
                name="pre_rnn_mlp",
            )(x)
        scan_lstm = nn.scan(
            ResetLSTMCell,
            variable_broadcast="params",
            split_rngs={"params": False},
            in_axes=0,
            out_axes=0,
        )(self.lstm_hidden_size, name="rnn")
        carry, out = scan_lstm(carry, (x, is_first))
        if self.post_rnn_apply:
            out = MLP(
                hidden_sizes=(self.post_rnn_dense_units,),
                activation=self.dense_act,
                norm_layer="layernorm" if self.post_rnn_layer_norm else None,
                name="post_rnn_mlp",
            )(out)
        values = MLP(
            output_dim=1,
            hidden_sizes=(self.critic_dense_units,) * self.critic_mlp_layers,
            activation=self.dense_act,
            norm_layer="layernorm" if self.critic_layer_norm else None,
            name="critic",
        )(out)
        actor_feat = MLP(
            hidden_sizes=(self.actor_dense_units,) * self.actor_mlp_layers,
            activation=self.dense_act,
            norm_layer="layernorm" if self.actor_layer_norm else None,
            name="actor_backbone",
        )(out)
        if self.is_continuous:
            pre = nn.Dense(int(sum(self.actions_dim)) * 2, name="actor_head")(actor_feat)
            mean, log_std = jnp.split(pre, 2, axis=-1)
            actor_out = [mean, log_std]
        else:
            actor_out = [
                nn.Dense(d, name=f"actor_head_{i}")(actor_feat)
                for i, d in enumerate(self.actions_dim)
            ]
        return actor_out, values, carry

    def initial_states(self, batch: int) -> Tuple[jax.Array, jax.Array]:
        return (
            jnp.zeros((batch, self.lstm_hidden_size)),
            jnp.zeros((batch, self.lstm_hidden_size)),
        )


class SequencePolicy(NamedTuple):
    """The sequence backbone (`models/sequence.py`) as the loop sees it: one token id an env step in, one id of the
    held vocabulary slice out; the recurrent carry is the per-env latent cache."""

    cfg: seq.SequenceConfig
    token_key: str


def backbone_name(cfg: Any) -> str:
    """`algo.backbone.name`: `lstm` (every recipe that says nothing) or the sequence model's."""
    return str(cfg.select("algo.backbone.name", "lstm") or "lstm")


def build_sequence_agent(dist: Any, cfg: Any, observation_space: gym.spaces.Dict, action_space: gym.Space, key: jax.Array,
                         params: Optional[Any] = None) -> Tuple[SequencePolicy, Any]:
    scfg = seq.SequenceConfig.from_node(cfg.algo.backbone)
    keys = list(cfg.algo.mlp_keys.encoder)
    if len(keys) != 1 or list(cfg.algo.cnn_keys.encoder):
        raise ValueError(f"a sequence policy reads one token key (`algo.mlp_keys.encoder`), got {keys}")
    space = observation_space[keys[0]]
    if not np.issubdtype(space.dtype, np.integer) or int(np.prod(space.shape)) != 1:
        raise ValueError(f"observation {keys[0]!r} must be one integer id a step, got {space}")
    if not isinstance(action_space, gym.spaces.Discrete) or int(action_space.n) != scfg.vocab_held:
        raise ValueError(f"the actions are the ids of the held slice, Discrete({scfg.vocab_held}); the env has {action_space}")
    if params is None:
        params = seq.init_params(scfg, key)
    return SequencePolicy(scfg, keys[0]), dist.replicate(params)


def build_agent(
    dist: Any,
    cfg: Any,
    observation_space: gym.spaces.Dict,
    action_space: gym.Space,
    key: jax.Array,
    params: Optional[Any] = None,
) -> Tuple[Any, Any]:
    """Construct module + params (reference agent.py:402-470 build_agent). The backbone is the recipe's choice."""
    if backbone_name(cfg) != "lstm":
        return build_sequence_agent(dist, cfg, observation_space, action_space, key, params)
    is_continuous = isinstance(action_space, gym.spaces.Box)
    if is_continuous:
        actions_dim = [int(np.prod(action_space.shape))]
    elif isinstance(action_space, gym.spaces.MultiDiscrete):
        actions_dim = [int(n) for n in action_space.nvec]
    else:
        actions_dim = [int(action_space.n)]
    enc = cfg.algo.encoder
    rnn = cfg.algo.rnn
    module = RecurrentPPOAgent(
        actions_dim=tuple(actions_dim),
        is_continuous=is_continuous,
        cnn_keys=tuple(cfg.algo.cnn_keys.encoder),
        mlp_keys=tuple(cfg.algo.mlp_keys.encoder),
        cnn_features_dim=int(enc.cnn_features_dim),
        mlp_features_dim=int(enc.mlp_features_dim),
        encoder_dense_units=int(enc.dense_units),
        encoder_mlp_layers=int(
            enc.mlp_layers
            if cfg.select("algo.encoder.mlp_layers") is not None
            else cfg.algo.mlp_layers
        ),
        dense_act=str(cfg.algo.dense_act),
        layer_norm=bool(cfg.algo.layer_norm),
        lstm_hidden_size=int(rnn.lstm.hidden_size),
        pre_rnn_apply=bool(rnn.pre_rnn_mlp.apply),
        pre_rnn_dense_units=int(rnn.pre_rnn_mlp.dense_units),
        pre_rnn_layer_norm=bool(rnn.pre_rnn_mlp.layer_norm),
        post_rnn_apply=bool(rnn.post_rnn_mlp.apply),
        post_rnn_dense_units=int(rnn.post_rnn_mlp.dense_units),
        post_rnn_layer_norm=bool(rnn.post_rnn_mlp.layer_norm),
        actor_dense_units=int(cfg.algo.actor.dense_units),
        actor_mlp_layers=int(cfg.algo.actor.mlp_layers),
        actor_layer_norm=bool(cfg.algo.actor.layer_norm),
        critic_dense_units=int(cfg.algo.critic.dense_units),
        critic_mlp_layers=int(cfg.algo.critic.mlp_layers),
        critic_layer_norm=bool(cfg.algo.critic.layer_norm),
    )
    if params is None:
        B = 1
        dummy_obs = {}
        for k in list(cfg.algo.cnn_keys.encoder) + list(cfg.algo.mlp_keys.encoder):
            shape = observation_space[k].shape
            dummy_obs[k] = jnp.zeros((1, B) + tuple(shape), dtype=jnp.float32)
        params = module.init(
            key,
            dummy_obs,
            jnp.zeros((1, B, int(sum(actions_dim)))),
            jnp.zeros((1, B, 1)),
            (jnp.zeros((B, int(rnn.lstm.hidden_size))), jnp.zeros((B, int(rnn.lstm.hidden_size)))),
        )["params"]
    params = dist.replicate(params)
    return module, params
