"""DreamerV3 agent (reference sheeprl/algos/dreamer_v3/agent.py, 1236 LoC).

TPU-native re-design of the DreamerV3 world model + actor-critic:

* `DV3CNNEncoder`/`DV3MLPEncoder` — 4-stage stride-2 convs (channels
  [1,2,4,8]·m, LN eps 1e-3, SiLU) and symlog-input MLPs (reference :42-153);
  NHWC layout throughout.
* `RSSM` — a Flax module whose `dynamic`/`imagination` single-step methods
  are built to sit inside `lax.scan` (the reference's python loops
  dreamer_v3.py:115-145 and :235-241 are the #1 pattern to redesign,
  SURVEY.md §7). Discrete stochastic state (32×32) with 1% unimix, masked
  `is_first` resets, learnable tanh initial recurrent state (reference
  :344-495).
* `Actor` — unimix one-hot-ST heads for discrete, scaled-Normal for
  continuous (reference :694-848).
* Hafner init (reference :1170-1180): xavier-normal everywhere; output heads
  scaled xavier-uniform — 0.0 (zeros) for reward/critic, 1.0 elsewhere.
* No `PlayerDV3` module (:596-693): the player is a pure jitted step
  function over (recurrent_state, stochastic_state, actions) carried on
  device — see `player_step` in dreamer_v3.py.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np

from ...distributions import (
    Independent,
    Normal,
    OneHotCategoricalStraightThrough,
)
from ...config.instantiate import locate
from ...models import MLP, LayerNorm, LayerNormGRUCell
from ...ops import symlog
from ...ops.wgrad_hoist import HoistableDense
from ...ops.conv_einsum import (
    EinsumConvTranspose4x4S2,
    conv4x4s2,
    phase_split_nhwc,
    resolve_conv_impl,
)

xavier_normal = nn.initializers.xavier_normal()


def uniform_init(scale: float):
    """reference dreamer_v3/utils.py `uniform_init_weights`: scaled
    xavier-uniform; scale 0.0 → zeros."""
    if scale == 0.0:
        return nn.initializers.zeros
    return nn.initializers.variance_scaling(scale, "fan_avg", "uniform")


def _uniform_mix(logits: jax.Array, unimix: float, discrete: int) -> jax.Array:
    """1% uniform mixing of categorical probs (reference agent.py:436-449)."""
    if unimix <= 0.0:
        return logits
    logits = logits.reshape(*logits.shape[:-1], -1, discrete)
    probs = jax.nn.softmax(logits, axis=-1)
    uniform = jnp.ones_like(probs) / discrete
    probs = (1 - unimix) * probs + unimix * uniform
    logits = jnp.log(probs)
    return logits.reshape(*logits.shape[:-2], -1)


def compute_stochastic_state(
    logits: jax.Array, discrete: int, key: Optional[jax.Array] = None, sample: bool = True
) -> jax.Array:
    """One-hot straight-through sample of the [*, S, D] categorical state
    (reference dreamer_v2/utils.py:44-61). Returns [*, S, D]."""
    logits = logits.reshape(*logits.shape[:-1], -1, discrete)
    dist = Independent(OneHotCategoricalStraightThrough(logits=logits), 1)
    if sample:
        assert key is not None
        return dist.rsample(key)
    return dist.base.mode


class DV3CNNEncoder(nn.Module):
    keys: Sequence[str]
    channels_multiplier: int
    stages: int = 4
    layer_norm: bool = True
    conv_impl: str = "auto"

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        einsum_convs = resolve_conv_impl(self.conv_impl)
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-1)
        lead = x.shape[:-3]
        x = x.reshape((-1,) + x.shape[-3:])
        for i in range(self.stages):
            x = conv4x4s2(
                (2**i) * self.channels_multiplier,
                padding=((1, 1), (1, 1)),
                use_bias=not self.layer_norm,
                kernel_init=xavier_normal,
                name=f"conv_{i}",
                einsum=einsum_convs,
            )(x)
            if self.layer_norm:
                x = LayerNorm(eps=1e-3)(x)
            x = nn.silu(x)
        x = x.reshape(lead + (-1,))
        return x


class DV3MLPEncoder(nn.Module):
    keys: Sequence[str]
    mlp_layers: int = 5
    dense_units: int = 1024
    layer_norm: bool = True
    symlog_inputs: bool = True

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate(
            [symlog(obs[k]) if self.symlog_inputs else obs[k] for k in self.keys], axis=-1
        )
        return MLP(
            hidden_sizes=(self.dense_units,) * self.mlp_layers,
            activation="silu",
            bias=not self.layer_norm,
            norm_layer="layernorm" if self.layer_norm else None,
            norm_args=[{"eps": 1e-3}] * self.mlp_layers if self.layer_norm else None,
            kernel_init=xavier_normal,
        )(x)


class DV3Encoder(nn.Module):
    cnn_keys: Sequence[str]
    mlp_keys: Sequence[str]
    cnn_channels_multiplier: int = 96
    mlp_layers: int = 5
    dense_units: int = 1024
    layer_norm: bool = True
    conv_impl: str = "auto"

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        feats = []
        if self.cnn_keys:
            feats.append(
                DV3CNNEncoder(
                    self.cnn_keys, self.cnn_channels_multiplier, conv_impl=self.conv_impl
                )(obs)
            )
        if self.mlp_keys:
            feats.append(
                DV3MLPEncoder(self.mlp_keys, self.mlp_layers, self.dense_units, self.layer_norm)(obs)
            )
        return jnp.concatenate(feats, axis=-1)


class DV3CNNDecoder(nn.Module):
    keys: Sequence[str]
    output_channels: Sequence[int]
    channels_multiplier: int
    image_size: Tuple[int, int] = (64, 64)
    stages: int = 4
    layer_norm: bool = True
    conv_impl: str = "auto"

    @nn.compact
    def __call__(self, latent: jax.Array, cnn_phases: bool = False) -> Dict[str, jax.Array]:
        """``cnn_phases=True`` (training loss only): the final deconv output
        stays in phase space [..., I, I, 2, 2, C] — see
        ops/conv_einsum.py:conv_transpose2d_k4s2p1. Per-key channel slicing
        is unchanged (channels are the trailing axis either way)."""
        einsum_convs = resolve_conv_impl(self.conv_impl)
        start = self.image_size[0] // (2**self.stages)
        c0 = (2 ** (self.stages - 1)) * self.channels_multiplier
        lead = latent.shape[:-1]
        x = nn.Dense(start * start * c0, kernel_init=xavier_normal, name="fc")(latent)
        x = x.reshape((-1, start, start, c0))
        for i in range(self.stages - 1):
            ch = (2 ** (self.stages - i - 2)) * self.channels_multiplier
            if einsum_convs:
                deconv = EinsumConvTranspose4x4S2(
                    ch,
                    use_bias=not self.layer_norm,
                    kernel_init=xavier_normal,
                    name=f"deconv_{i}",
                )
            else:
                deconv = nn.ConvTranspose(
                    ch,
                    (4, 4),
                    strides=(2, 2),
                    padding=((2, 2), (2, 2)),  # torch k4 s2 p1 ≡ flax pad k-1-p=2
                    use_bias=not self.layer_norm,
                    transpose_kernel=True,
                    kernel_init=xavier_normal,
                    name=f"deconv_{i}",
                )
            x = deconv(x)
            if self.layer_norm:
                x = LayerNorm(eps=1e-3)(x)
            x = nn.silu(x)
        if einsum_convs:
            x = EinsumConvTranspose4x4S2(
                sum(self.output_channels), kernel_init=uniform_init(1.0), name="to_obs"
            )(x, phases=cnn_phases)
        else:
            x = nn.ConvTranspose(
                sum(self.output_channels),
                (4, 4),
                strides=(2, 2),
                padding=((2, 2), (2, 2)),
                transpose_kernel=True,
                kernel_init=uniform_init(1.0),
                name="to_obs",
            )(x)
            if cnn_phases:
                x = phase_split_nhwc(x)
        x = x.reshape(lead + x.shape[1:])
        out: Dict[str, jax.Array] = {}
        start_ch = 0
        for k, ch in zip(self.keys, self.output_channels):
            out[k] = x[..., start_ch : start_ch + ch]
            start_ch += ch
        return out


class DV3MLPDecoder(nn.Module):
    keys: Sequence[str]
    output_dims: Sequence[int]
    mlp_layers: int = 5
    dense_units: int = 1024
    layer_norm: bool = True

    @nn.compact
    def __call__(self, latent: jax.Array) -> Dict[str, jax.Array]:
        x = MLP(
            hidden_sizes=(self.dense_units,) * self.mlp_layers,
            activation="silu",
            bias=not self.layer_norm,
            norm_layer="layernorm" if self.layer_norm else None,
            norm_args=[{"eps": 1e-3}] * self.mlp_layers if self.layer_norm else None,
            kernel_init=xavier_normal,
        )(latent)
        return {
            k: nn.Dense(d, kernel_init=uniform_init(1.0), name=f"head_{k}")(x)
            for k, d in zip(self.keys, self.output_dims)
        }


class DV3Decoder(nn.Module):
    cnn_keys: Sequence[str]
    mlp_keys: Sequence[str]
    cnn_output_channels: Sequence[int]
    mlp_output_dims: Sequence[int]
    cnn_channels_multiplier: int = 96
    image_size: Tuple[int, int] = (64, 64)
    mlp_layers: int = 5
    dense_units: int = 1024
    layer_norm: bool = True
    conv_impl: str = "auto"

    @nn.compact
    def __call__(self, latent: jax.Array, cnn_phases: bool = False) -> Dict[str, jax.Array]:
        out: Dict[str, jax.Array] = {}
        if self.cnn_keys:
            out.update(
                DV3CNNDecoder(
                    self.cnn_keys,
                    self.cnn_output_channels,
                    self.cnn_channels_multiplier,
                    self.image_size,
                    conv_impl=self.conv_impl,
                )(latent, cnn_phases=cnn_phases)
            )
        if self.mlp_keys:
            out.update(
                DV3MLPDecoder(self.mlp_keys, self.mlp_output_dims, self.mlp_layers, self.dense_units)(latent)
            )
        return out


class RecurrentModel(nn.Module):
    """Dense(no-bias)+LN+SiLU → fused LayerNormGRUCell (reference :281-342).

    `features` (the pre-GRU half) is exposed separately: with DecoupledRSSM
    the GRU inputs are known for the whole sequence up front, so the feature
    matmul runs time-parallel and only the GRU recurrence stays sequential —
    optionally as the VMEM-resident Pallas kernel (ops/pallas_gru.py).
    Attribute names keep the original param-tree layout (mlp / LayerNorm_0 /
    gru) so existing checkpoints load unchanged."""

    recurrent_state_size: int
    dense_units: int

    def setup(self) -> None:
        self.mlp = HoistableDense(self.dense_units, use_bias=False, kernel_init=xavier_normal)
        self.LayerNorm_0 = LayerNorm(eps=1e-3)
        self.gru = LayerNormGRUCell(self.recurrent_state_size, use_bias=False)

    def features(self, x: jax.Array) -> jax.Array:
        return nn.silu(self.LayerNorm_0(self.mlp(x)))

    def __call__(self, x: jax.Array, h: jax.Array) -> jax.Array:
        new_h, _ = self.gru(h, self.features(x))
        return new_h


class _StochHead(nn.Module):
    """hidden MLP (1 layer) + logits head for transition/representation."""

    hidden_size: int
    stoch_logits: int
    layer_norm: bool = True

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        # named as `nn.Dense` names itself, so that the tree keeps its paths
        x = HoistableDense(
            self.hidden_size, use_bias=not self.layer_norm, kernel_init=xavier_normal, name="Dense_0"
        )(x)
        if self.layer_norm:
            x = LayerNorm(eps=1e-3)(x)
        x = nn.silu(x)
        return HoistableDense(self.stoch_logits, kernel_init=uniform_init(1.0), name="logits")(x)


class RSSM(nn.Module):
    """Recurrent State-Space Model (reference agent.py:344-495).

    Methods (each one step, scan-ready):
    * `initial_states(batch)` → (h0, z0_flat)
    * `dynamic(posterior, h, action, embed, is_first, key)` →
      (h, posterior, prior, post_logits, prior_logits)
    * `imagination(prior_flat, h, action, key)` → (prior_flat, h)
    """

    stochastic_size: int = 32
    discrete_size: int = 32
    recurrent_state_size: int = 4096
    dense_units: int = 1024
    hidden_size: int = 1024
    representation_hidden_size: Optional[int] = None  # defaults to hidden_size
    unimix: float = 0.01
    learnable_initial_recurrent_state: bool = True
    # DecoupledRSSM (reference agent.py:501-593): the posterior is a function
    # of the embedded observation ALONE, so the whole [T, B] posterior batch
    # is one time-parallel MLP application — only the GRU + prior remain in
    # the scan. TPU-wise this moves most representation FLOPs out of the
    # sequential chain and onto big MXU-friendly batched matmuls.
    decoupled: bool = False

    def setup(self) -> None:
        self.recurrent_model = RecurrentModel(self.recurrent_state_size, self.dense_units)
        stoch_logits = self.stochastic_size * self.discrete_size
        self.representation_model = _StochHead(
            self.representation_hidden_size or self.hidden_size, stoch_logits, name="representation"
        )
        self.transition_model = _StochHead(self.hidden_size, stoch_logits, name="transition")
        if self.learnable_initial_recurrent_state:
            self.initial_recurrent_state = self.param(
                "initial_recurrent_state",
                nn.initializers.zeros,
                (self.recurrent_state_size,),
            )
        else:
            self.initial_recurrent_state = jnp.zeros((self.recurrent_state_size,))

    def _transition(self, recurrent_out: jax.Array) -> jax.Array:
        logits = self.transition_model(recurrent_out)
        return _uniform_mix(logits, self.unimix, self.discrete_size)

    def _representation(self, recurrent_state: jax.Array, embedded_obs: jax.Array) -> jax.Array:
        if self.decoupled:
            # reference DecoupledRSSM._representation (agent.py:582-593):
            # posterior from the embedding alone, no recurrent input
            logits = self.representation_model(embedded_obs)
        else:
            logits = self.representation_model(jnp.concatenate([recurrent_state, embedded_obs], -1))
        return _uniform_mix(logits, self.unimix, self.discrete_size)

    def initial_states(self, batch_shape: Sequence[int]) -> Tuple[jax.Array, jax.Array]:
        h0 = jnp.tanh(self.initial_recurrent_state)
        h0 = jnp.broadcast_to(h0, tuple(batch_shape) + h0.shape)
        z0_logits = self._transition(h0)
        z0 = compute_stochastic_state(z0_logits, self.discrete_size, sample=False)
        return h0, z0.reshape(*z0.shape[:-2], -1)

    def dynamic(
        self,
        posterior: jax.Array,  # [B, S*D] flat
        recurrent_state: jax.Array,  # [B, R]
        action: jax.Array,  # [B, A]
        embedded_obs: jax.Array,  # [B, E]
        is_first: jax.Array,  # [B, 1]
        key: jax.Array,
    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        action = (1 - is_first) * action
        h0, z0 = self.initial_states(recurrent_state.shape[:-1])
        recurrent_state = (1 - is_first) * recurrent_state + is_first * h0
        posterior = (1 - is_first) * posterior + is_first * z0
        recurrent_state = self.recurrent_model(
            jnp.concatenate([posterior, action], -1), recurrent_state
        )
        prior_logits = self._transition(recurrent_state)
        posterior_logits = self._representation(recurrent_state, embedded_obs)
        new_posterior = compute_stochastic_state(posterior_logits, self.discrete_size, key)
        new_posterior = new_posterior.reshape(*new_posterior.shape[:-2], -1)
        return recurrent_state, new_posterior, posterior_logits, prior_logits

    def imagination(
        self, prior: jax.Array, recurrent_state: jax.Array, action: jax.Array, key: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        recurrent_state = self.recurrent_model(
            jnp.concatenate([prior, action], -1), recurrent_state
        )
        logits = self._transition(recurrent_state)
        imagined_prior = compute_stochastic_state(logits, self.discrete_size, key)
        return imagined_prior.reshape(*imagined_prior.shape[:-2], -1), recurrent_state

    def recurrent_features(self, z_and_a: jax.Array) -> jax.Array:
        """Pre-GRU feature half of the recurrent model, time-batched (the
        Pallas decoupled path, dreamer_v3.py)."""
        return self.recurrent_model.features(z_and_a)

    def representation_logits(self, embedded_obs: jax.Array) -> jax.Array:
        """Decoupled posterior logits for a whole [T, B, E] embedding batch at
        once (reference DecoupledRSSM usage, dreamer_v3.py:115-129, where
        `_representation` runs over the full sequence before the loop)."""
        logits = self.representation_model(embedded_obs)
        return _uniform_mix(logits, self.unimix, self.discrete_size)

    def dynamic_decoupled(
        self,
        posterior: jax.Array,  # [B, S*D] flat — PREVIOUS step's precomputed posterior
        recurrent_state: jax.Array,  # [B, R]
        action: jax.Array,  # [B, A]
        is_first: jax.Array,  # [B, 1]
    ) -> Tuple[jax.Array, jax.Array]:
        """One decoupled dynamics step (reference DecoupledRSSM.dynamic,
        agent.py:542-580): only the recurrent state and the prior logits are
        sequential; the posterior is an input, not an output."""
        action = (1 - is_first) * action
        h0, z0 = self.initial_states(recurrent_state.shape[:-1])
        recurrent_state = (1 - is_first) * recurrent_state + is_first * h0
        posterior = (1 - is_first) * posterior + is_first * z0
        recurrent_state = self.recurrent_model(
            jnp.concatenate([posterior, action], -1), recurrent_state
        )
        prior_logits = self._transition(recurrent_state)
        return recurrent_state, prior_logits

    def representation_step(
        self, recurrent_state: jax.Array, embedded_obs: jax.Array, key: jax.Array
    ) -> jax.Array:
        logits = self._representation(recurrent_state, embedded_obs)
        z = compute_stochastic_state(logits, self.discrete_size, key)
        return z.reshape(*z.shape[:-2], -1)

    def __call__(self, posterior, recurrent_state, action, embedded_obs, is_first, key):
        # default apply path (used for init only)
        return self.dynamic(posterior, recurrent_state, action, embedded_obs, is_first, key)


class DV3Head(nn.Module):
    """MLP trunk + linear head (reward / continue / critic, reference
    build_agent :935-1160). `out_scale` drives the Hafner output init."""

    output_dim: int
    mlp_layers: int = 5
    dense_units: int = 1024
    layer_norm: bool = True
    out_scale: float = 0.0

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = MLP(
            hidden_sizes=(self.dense_units,) * self.mlp_layers,
            activation="silu",
            bias=not self.layer_norm,
            norm_layer="layernorm" if self.layer_norm else None,
            norm_args=[{"eps": 1e-3}] * self.mlp_layers if self.layer_norm else None,
            kernel_init=xavier_normal,
        )(x)
        return nn.Dense(self.output_dim, kernel_init=uniform_init(self.out_scale), name="out")(x)


class WorldModel(nn.Module):
    """Encoder + RSSM + decoder + reward + continue (reference :1128-1160)."""

    cnn_keys: Sequence[str]
    mlp_keys: Sequence[str]
    cnn_output_channels: Sequence[int]
    mlp_output_dims: Sequence[int]
    image_size: Tuple[int, int]
    cnn_channels_multiplier: int
    mlp_layers: int
    dense_units: int
    stochastic_size: int
    discrete_size: int
    recurrent_state_size: int
    hidden_size: int
    unimix: float
    reward_bins: int = 255
    learnable_initial_recurrent_state: bool = True
    decoupled_rssm: bool = False
    # per-submodule overrides (reference honors each configs/algo key
    # independently: encoder/observation_model/reward/discount dense_units &
    # mlp_layers, recurrent_model.dense_units, representation hidden_size)
    representation_hidden_size: Optional[int] = None
    recurrent_dense_units: Optional[int] = None
    decoder_cnn_channels_multiplier: Optional[int] = None
    encoder_mlp_layers: Optional[int] = None
    encoder_dense_units: Optional[int] = None
    decoder_mlp_layers: Optional[int] = None
    decoder_dense_units: Optional[int] = None
    reward_mlp_layers: Optional[int] = None
    reward_dense_units: Optional[int] = None
    continue_mlp_layers: Optional[int] = None
    continue_dense_units: Optional[int] = None
    conv_impl: str = "auto"

    def setup(self) -> None:
        self.encoder = DV3Encoder(
            cnn_keys=self.cnn_keys,
            mlp_keys=self.mlp_keys,
            cnn_channels_multiplier=self.cnn_channels_multiplier,
            mlp_layers=self.encoder_mlp_layers or self.mlp_layers,
            dense_units=self.encoder_dense_units or self.dense_units,
            conv_impl=self.conv_impl,
        )
        self.rssm = RSSM(
            stochastic_size=self.stochastic_size,
            discrete_size=self.discrete_size,
            recurrent_state_size=self.recurrent_state_size,
            dense_units=self.recurrent_dense_units or self.dense_units,
            hidden_size=self.hidden_size,
            representation_hidden_size=self.representation_hidden_size,
            unimix=self.unimix,
            learnable_initial_recurrent_state=self.learnable_initial_recurrent_state,
            decoupled=self.decoupled_rssm,
        )
        self.observation_model = DV3Decoder(
            cnn_keys=self.cnn_keys,
            mlp_keys=self.mlp_keys,
            cnn_output_channels=self.cnn_output_channels,
            mlp_output_dims=self.mlp_output_dims,
            cnn_channels_multiplier=self.decoder_cnn_channels_multiplier or self.cnn_channels_multiplier,
            image_size=self.image_size,
            mlp_layers=self.decoder_mlp_layers or self.mlp_layers,
            dense_units=self.decoder_dense_units or self.dense_units,
            conv_impl=self.conv_impl,
        )
        self.reward_model = DV3Head(
            self.reward_bins,
            self.reward_mlp_layers or self.mlp_layers,
            self.reward_dense_units or self.dense_units,
            out_scale=0.0,
            name="reward",
        )
        self.continue_model = DV3Head(
            1,
            self.continue_mlp_layers or self.mlp_layers,
            self.continue_dense_units or self.dense_units,
            out_scale=1.0,
            name="continue",
        )

    # ---- method entry points (module.apply(..., method=...)) -------------
    def embed(self, obs: Dict[str, jax.Array]) -> jax.Array:
        return self.encoder(obs)

    def dynamic(self, posterior, recurrent_state, action, embedded_obs, is_first, key):
        return self.rssm.dynamic(posterior, recurrent_state, action, embedded_obs, is_first, key)

    def imagination(self, prior, recurrent_state, action, key):
        return self.rssm.imagination(prior, recurrent_state, action, key)

    def initial_states(self, batch_shape: Sequence[int]):
        return self.rssm.initial_states(batch_shape)

    def recurrent_step(self, stoch_and_action: jax.Array, recurrent_state: jax.Array) -> jax.Array:
        return self.rssm.recurrent_model(stoch_and_action, recurrent_state)

    def representation_step(self, recurrent_state, embedded_obs, key):
        return self.rssm.representation_step(recurrent_state, embedded_obs, key)

    def representation_logits(self, embedded_obs):
        return self.rssm.representation_logits(embedded_obs)

    def recurrent_features(self, z_and_a):
        return self.rssm.recurrent_features(z_and_a)

    def transition_logits(self, recurrent_state):
        return self.rssm._transition(recurrent_state)

    def dynamic_decoupled(self, posterior, recurrent_state, action, is_first):
        return self.rssm.dynamic_decoupled(posterior, recurrent_state, action, is_first)

    def decode(self, latent: jax.Array) -> Dict[str, jax.Array]:
        return self.observation_model(latent)

    def decode_phases(self, latent: jax.Array) -> Dict[str, jax.Array]:
        """Training-loss decode: cnn outputs in phase space ([..., I, I, 2,
        2, C]); the MSE against a `phase_split_nhwc` target sums to exactly
        the pixel-space observation loss, without the depth-to-space
        interleave (and, crucially, without its backward transpose)."""
        return self.observation_model(latent, cnn_phases=True)

    def reward(self, latent: jax.Array) -> jax.Array:
        return self.reward_model(latent)

    def cont(self, latent: jax.Array) -> jax.Array:
        return self.continue_model(latent)

    def __call__(self, obs, posterior, recurrent_state, action, is_first, key):
        """Init path: touches every submodule once."""
        embedded = self.encoder(obs)
        h, post, post_logits, prior_logits = self.rssm.dynamic(
            posterior, recurrent_state, action, embedded, is_first, key
        )
        latent = jnp.concatenate([post, h], -1)
        return (
            self.observation_model(latent),
            self.reward_model(latent),
            self.continue_model(latent),
            post_logits,
            prior_logits,
        )


class Actor(nn.Module):
    """DV3 actor (reference :694-848): MLP trunk; one unimix one-hot-ST head
    per discrete dim, or a scaled-Normal head for continuous actions."""

    actions_dim: Sequence[int]
    is_continuous: bool
    mlp_layers: int = 5
    dense_units: int = 1024
    layer_norm: bool = True
    unimix: float = 0.01
    init_std: float = 2.0
    min_std: float = 0.1
    max_std: float = 1.0
    action_clip: float = 1.0

    @nn.compact
    def __call__(self, state: jax.Array) -> List[jax.Array]:
        x = MLP(
            hidden_sizes=(self.dense_units,) * self.mlp_layers,
            activation="silu",
            bias=not self.layer_norm,
            norm_layer="layernorm" if self.layer_norm else None,
            norm_args=[{"eps": 1e-3}] * self.mlp_layers if self.layer_norm else None,
            kernel_init=xavier_normal,
        )(state)
        if self.is_continuous:
            out = nn.Dense(sum(self.actions_dim) * 2, kernel_init=uniform_init(1.0), name="head")(x)
            return [out]
        return [
            nn.Dense(d, kernel_init=uniform_init(1.0), name=f"head_{i}")(x)
            for i, d in enumerate(self.actions_dim)
        ]


# Finite stand-in for the reference's `-inf` logit masking (agent.py:907-924):
# exp(MASK_LOGIT - lse) underflows to exactly 0.0, so masked actions get zero
# probability while entropy/log-prob stay NaN-free inside jit.
MASK_LOGIT = -1e9


class MinedojoActor(Actor):
    """DV3 actor with MineDojo action masking (reference agent.py:848-933).

    Same parameter structure as `Actor` (the forward pass is inherited);
    masking happens at sampling time in `sample_actor_actions`:
    * head 0 (action type) is masked by `mask_action_type`;
    * head 1 (craft/smelt arg) is masked by `mask_craft_smelt` where the
      sampled action type is 15 (craft);
    * head 2 (item arg) is masked by `mask_equip_place` where the action type
      is 16/17 (equip/place) and by `mask_destroy` where it is 18 (destroy).
    The reference's per-(t, b) python loops (:910-924) become vectorised
    `jnp.where` updates over the whole batch.
    """

    masked_heads: bool = True


def apply_minedojo_masks(
    pre_dist: List[jax.Array],
    mask: Dict[str, jax.Array],
    functional_action: Optional[jax.Array] = None,
) -> List[jax.Array]:
    """Mask each head's (unimixed) logits. `functional_action` is the sampled
    head-0 action index ([...]-shaped); when None (head 0 not yet sampled)
    only head 0 is masked — callers re-invoke for heads 1-2 after sampling
    head 0, mirroring the reference's sequential head loop."""
    out = list(pre_dist)
    if "mask_action_type" in mask:
        m = jnp.broadcast_to(mask["mask_action_type"], out[0].shape)
        out[0] = jnp.where(m, out[0], MASK_LOGIT)
    if functional_action is None:
        return out
    fa = functional_action[..., None]
    if len(out) > 1 and "mask_craft_smelt" in mask:
        m = jnp.broadcast_to(mask["mask_craft_smelt"], out[1].shape)
        out[1] = jnp.where((fa == 15) & ~m, MASK_LOGIT, out[1])
    if len(out) > 2:
        if "mask_equip_place" in mask:
            m = jnp.broadcast_to(mask["mask_equip_place"], out[2].shape)
            out[2] = jnp.where(((fa == 16) | (fa == 17)) & ~m, MASK_LOGIT, out[2])
        if "mask_destroy" in mask:
            m = jnp.broadcast_to(mask["mask_destroy"], out[2].shape)
            out[2] = jnp.where((fa == 18) & ~m, MASK_LOGIT, out[2])
    return out


def actor_dists(actor: Actor, pre_dist: List[jax.Array]):
    """Build the per-head distributions from the actor's raw outputs."""
    if actor.is_continuous:
        mean, std = jnp.split(pre_dist[0], 2, axis=-1)
        std = (actor.max_std - actor.min_std) * jax.nn.sigmoid(std + actor.init_std) + actor.min_std
        return [Independent(Normal(jnp.tanh(mean), std), 1)]
    dists = []
    for logits in pre_dist:
        mixed = _uniform_mix(logits, actor.unimix, logits.shape[-1])
        dists.append(OneHotCategoricalStraightThrough(logits=mixed))
    return dists


def sample_actor_actions(
    actor: Actor,
    pre_dist: List[jax.Array],
    key: Optional[jax.Array],
    greedy: bool = False,
    mask: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[List[jax.Array], List[Any]]:
    """Sample (or take the mode of) each action head (reference :788-825).
    With a `mask` dict and a masking actor (MinedojoActor), heads are sampled
    sequentially: head 0's sample gates the masks on heads 1-2 (reference
    MinedojoActor.forward, agent.py:899-932)."""
    if mask and getattr(actor, "masked_heads", False) and not actor.is_continuous:
        mixed = [_uniform_mix(l, actor.unimix, l.shape[-1]) for l in pre_dist]
        mixed = apply_minedojo_masks(mixed, mask)
        keys = jax.random.split(key, len(mixed)) if key is not None else [None] * len(mixed)
        d0 = OneHotCategoricalStraightThrough(logits=mixed[0])
        a0 = d0.mode if greedy or keys[0] is None else d0.rsample(keys[0])
        functional_action = jnp.argmax(a0, axis=-1)
        mixed = apply_minedojo_masks(mixed, mask, functional_action)
        dists = [OneHotCategoricalStraightThrough(logits=l) for l in mixed]
        actions = [a0]
        for d, k in zip(dists[1:], keys[1:]):
            actions.append(d.mode if greedy or k is None else d.rsample(k))
        return actions, dists
    dists = actor_dists(actor, pre_dist)
    actions: List[jax.Array] = []
    if actor.is_continuous:
        dist = dists[0]
        if greedy or key is None:
            act = dist.mode
        else:
            act = dist.rsample(key)
        if actor.action_clip > 0:
            clip = jnp.full_like(act, actor.action_clip)
            act = act * jax.lax.stop_gradient(clip / jnp.maximum(clip, jnp.abs(act)))
        actions.append(act)
    else:
        keys = jax.random.split(key, len(dists)) if key is not None else [None] * len(dists)
        for d, k in zip(dists, keys):
            actions.append(d.mode if greedy or k is None else d.rsample(k))
    return actions, dists


def build_agent(
    dist: Any,
    cfg: Any,
    observation_space: gym.spaces.Dict,
    actions_dim: Sequence[int],
    is_continuous: bool,
    key: jax.Array,
    state: Optional[Dict[str, Any]] = None,
):
    """Construct (world_model, actor, critic modules, params) — reference
    build_agent (agent.py:935-1235). params = {wm, actor, critic,
    target_critic}."""
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    wm_cfg = cfg.algo.world_model
    screen = int(cfg.env.screen_size)
    world_model = WorldModel(
        cnn_keys=cnn_keys,
        mlp_keys=mlp_keys,
        cnn_output_channels=[observation_space[k].shape[-1] for k in cnn_keys],
        mlp_output_dims=[int(np.prod(observation_space[k].shape)) for k in mlp_keys],
        image_size=(screen, screen),
        cnn_channels_multiplier=int(wm_cfg.encoder.cnn_channels_multiplier),
        mlp_layers=int(cfg.algo.mlp_layers),
        dense_units=int(cfg.algo.dense_units),
        stochastic_size=int(wm_cfg.stochastic_size),
        discrete_size=int(wm_cfg.discrete_size),
        recurrent_state_size=int(wm_cfg.recurrent_model.recurrent_state_size),
        hidden_size=int(wm_cfg.transition_model.hidden_size),
        unimix=float(cfg.algo.unimix),
        reward_bins=int(wm_cfg.reward_model.bins),
        learnable_initial_recurrent_state=bool(wm_cfg.learnable_initial_recurrent_state),
        decoupled_rssm=bool(wm_cfg.select("decoupled_rssm") or False),
        conv_impl=str(wm_cfg.select("conv_impl", "auto")),
        representation_hidden_size=int(wm_cfg.representation_model.hidden_size),
        recurrent_dense_units=int(wm_cfg.recurrent_model.dense_units),
        decoder_cnn_channels_multiplier=int(wm_cfg.observation_model.cnn_channels_multiplier),
        encoder_mlp_layers=int(wm_cfg.encoder.mlp_layers),
        encoder_dense_units=int(wm_cfg.encoder.dense_units),
        decoder_mlp_layers=int(wm_cfg.observation_model.mlp_layers),
        decoder_dense_units=int(wm_cfg.observation_model.dense_units),
        reward_mlp_layers=int(wm_cfg.reward_model.mlp_layers),
        reward_dense_units=int(wm_cfg.reward_model.dense_units),
        continue_mlp_layers=int(wm_cfg.discount_model.mlp_layers),
        continue_dense_units=int(wm_cfg.discount_model.dense_units),
    )
    latent_size = int(wm_cfg.stochastic_size) * int(wm_cfg.discrete_size) + int(
        wm_cfg.recurrent_model.recurrent_state_size
    )
    # `_target_`-selectable actor class (reference agent.py:1136):
    # `algo.actor.cls` picks Actor or MinedojoActor
    actor_cls = locate(str(cfg.algo.actor.select("cls") or f"{__name__}.Actor"))
    actor = actor_cls(
        actions_dim=tuple(actions_dim),
        is_continuous=is_continuous,
        mlp_layers=int(cfg.algo.actor.mlp_layers),
        dense_units=int(cfg.algo.actor.dense_units),
        unimix=float(cfg.algo.actor.unimix),
        init_std=float(cfg.algo.actor.init_std),
        min_std=float(cfg.algo.actor.min_std),
        max_std=float(cfg.algo.actor.max_std),
        action_clip=float(cfg.algo.actor.action_clip),
    )
    critic = DV3Head(
        int(cfg.algo.critic.bins),
        int(cfg.algo.critic.mlp_layers),
        int(cfg.algo.critic.dense_units),
        out_scale=0.0,
    )
    if state is not None:
        params = state
    else:
        kw, ka, kc, ks = jax.random.split(key, 4)
        B = 1
        dummy_obs = {}
        for k in cnn_keys:
            dummy_obs[k] = jnp.zeros((B,) + tuple(observation_space[k].shape), jnp.float32)
        for k in mlp_keys:
            dummy_obs[k] = jnp.zeros((B, int(np.prod(observation_space[k].shape))), jnp.float32)
        stoch_flat = int(wm_cfg.stochastic_size) * int(wm_cfg.discrete_size)
        wm_params = world_model.init(
            {"params": kw},
            dummy_obs,
            jnp.zeros((B, stoch_flat)),
            jnp.zeros((B, int(wm_cfg.recurrent_model.recurrent_state_size))),
            jnp.zeros((B, int(sum(actions_dim)))),
            jnp.zeros((B, 1)),
            ks,
        )["params"]
        actor_params = actor.init(ka, jnp.zeros((B, latent_size)))["params"]
        critic_params = critic.init(kc, jnp.zeros((B, latent_size)))["params"]
        params = {
            "wm": wm_params,
            "actor": actor_params,
            "critic": critic_params,
            "target_critic": jax.tree.map(jnp.copy, critic_params),
        }
    params = dist.replicate(params)
    return world_model, actor, critic, params
