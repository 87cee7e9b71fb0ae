"""Core NN building blocks (Flax).

TPU-native re-design of the reference's torch model zoo
(sheeprl/models/models.py): `MLP` (:16-119), `CNN` (:122-202), `DeCNN`
(:205-285), `NatureCNN` (:288-328), `LayerNormGRUCell` (:331-410),
`MultiEncoder`/`MultiDecoder` (:413-504), `LayerNormChannelLast` (:507-525).

Design notes:
* Images are NHWC (TPU-native layout) — the reference is NCHW; `MultiEncoder`
  accepts dict observations with image values [..., H, W, C].
* `LayerNormGRUCell` is a *fused* cell: one matmul of [x, h] against a single
  3H kernel + LN + gate math, built to sit inside `lax.scan` (the RSSM hot
  loop, reference dreamer_v3.py:115-145).
* Norm/activation are configured by name (string) to stay yaml-friendly.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.wgrad_hoist import HoistableDense

Dtype = Any

_ACTIVATIONS: Dict[str, Callable] = {
    "relu": nn.relu,
    "tanh": jnp.tanh,
    "silu": nn.silu,
    "swish": nn.silu,
    "gelu": nn.gelu,
    "elu": nn.elu,
    "leaky_relu": nn.leaky_relu,
    "sigmoid": nn.sigmoid,
    "identity": lambda x: x,
    "none": lambda x: x,
}


def get_activation(name: Optional[str]) -> Callable:
    if name is None:
        return lambda x: x
    if callable(name):
        return name
    # accept torch-style class paths from parity configs, e.g. "torch.nn.SiLU"
    key = str(name).rsplit(".", 1)[-1].lower()
    if key not in _ACTIVATIONS:
        raise ValueError(f"Unknown activation '{name}'")
    return _ACTIVATIONS[key]


class LayerNorm(nn.Module):
    """Dtype-preserving LayerNorm (reference models.py:507-512)."""

    eps: float = 1e-5
    use_scale: bool = True
    use_bias: bool = True

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        dtype = x.dtype
        out = nn.LayerNorm(epsilon=self.eps, use_scale=self.use_scale, use_bias=self.use_bias)(
            x.astype(jnp.float32)
        )
        return out.astype(dtype)


# NHWC means "channel last" is the native layout: the reference's
# LayerNormChannelLast permute (models.py:515-525) is a no-op here.
LayerNormChannelLast = LayerNorm


def _norm(name: Optional[str], **kwargs: Any) -> Optional[Callable]:
    if name in (None, "none", False):
        return None
    key = str(name).rsplit(".", 1)[-1].lower()
    if key in ("layernorm", "layernormchannellast"):
        return LayerNorm(**{k: v for k, v in kwargs.items() if k in ("eps", "use_scale", "use_bias")})
    raise ValueError(f"Unknown norm layer '{name}'")


class MLP(nn.Module):
    """Linear stack with optional per-layer dropout/norm/activation and an
    optional `output_dim` head (reference models.py:16-119, Tianshou-style
    miniblocks: Linear → Dropout → Norm → Act)."""

    hidden_sizes: Sequence[int] = ()
    output_dim: Optional[int] = None
    activation: Any = "tanh"
    norm_layer: Any = None
    norm_args: Optional[Sequence[Dict[str, Any]]] = None
    dropout: float = 0.0
    flatten_dim: Optional[int] = None
    bias: bool = True
    dtype: Dtype = jnp.float32
    kernel_init: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> jax.Array:
        if self.flatten_dim is not None:
            x = jnp.reshape(x, x.shape[: self.flatten_dim] + (-1,))
        act = get_activation(self.activation)
        dense_kw = {} if self.kernel_init is None else {"kernel_init": self.kernel_init}
        for i, h in enumerate(self.hidden_sizes):
            x = nn.Dense(h, use_bias=self.bias, dtype=self.dtype, name=f"dense_{i}", **dense_kw)(x)
            if self.dropout > 0:
                x = nn.Dropout(self.dropout, deterministic=deterministic)(x)
            norm_args = (self.norm_args[i] if self.norm_args else {}) if self.norm_layer else {}
            norm = _norm(self.norm_layer, **norm_args)
            if norm is not None:
                x = norm(x)
            x = act(x)
        if self.output_dim is not None:
            x = nn.Dense(self.output_dim, use_bias=self.bias, dtype=self.dtype, name="out", **dense_kw)(x)
        return x


class CNN(nn.Module):
    """Generic conv stack, NHWC (reference models.py:122-202)."""

    channels: Sequence[int]
    kernel_sizes: Sequence[int] = (3,)
    strides: Sequence[int] = (1,)
    paddings: Any = "SAME"
    activation: Any = "relu"
    norm_layer: Any = None
    norm_args: Optional[Sequence[Dict[str, Any]]] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        act = get_activation(self.activation)
        n = len(self.channels)
        ks = list(self.kernel_sizes) * n if len(self.kernel_sizes) == 1 else list(self.kernel_sizes)
        st = list(self.strides) * n if len(self.strides) == 1 else list(self.strides)
        for i, ch in enumerate(self.channels):
            pad = self.paddings if isinstance(self.paddings, str) else self.paddings[i]
            x = nn.Conv(
                ch,
                kernel_size=(ks[i], ks[i]),
                strides=(st[i], st[i]),
                padding=pad,
                dtype=self.dtype,
                name=f"conv_{i}",
            )(x)
            norm_args = (self.norm_args[i] if self.norm_args else {}) if self.norm_layer else {}
            norm = _norm(self.norm_layer, **norm_args)
            if norm is not None:
                x = norm(x)
            x = act(x)
        return x


class DeCNN(nn.Module):
    """Transposed-conv stack, NHWC (reference models.py:205-285). The last
    layer gets no norm/activation (it produces the reconstruction)."""

    channels: Sequence[int]
    kernel_sizes: Sequence[int] = (4,)
    strides: Sequence[int] = (2,)
    paddings: Any = "SAME"
    activation: Any = "relu"
    norm_layer: Any = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        act = get_activation(self.activation)
        n = len(self.channels)
        ks = list(self.kernel_sizes) * n if len(self.kernel_sizes) == 1 else list(self.kernel_sizes)
        st = list(self.strides) * n if len(self.strides) == 1 else list(self.strides)
        for i, ch in enumerate(self.channels):
            pad = self.paddings if isinstance(self.paddings, str) else self.paddings[i]
            x = nn.ConvTranspose(
                ch,
                kernel_size=(ks[i], ks[i]),
                strides=(st[i], st[i]),
                padding=pad,
                dtype=self.dtype,
                name=f"deconv_{i}",
            )(x)
            if i < n - 1:
                norm = _norm(self.norm_layer)
                if norm is not None:
                    x = norm(x)
                x = act(x)
        return x


class NatureCNN(nn.Module):
    """DQN-Nature encoder: 3 convs + fc (reference models.py:288-328).

    Output feature dim is `features_dim`; input is [..., H, W, C] uint8/float.
    """

    features_dim: int = 512
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = x.astype(self.dtype) / 255.0
        lead = x.shape[:-3]
        x = jnp.reshape(x, (-1,) + x.shape[-3:])
        x = nn.relu(nn.Conv(32, (8, 8), strides=(4, 4), padding="VALID", dtype=self.dtype)(x))
        x = nn.relu(nn.Conv(64, (4, 4), strides=(2, 2), padding="VALID", dtype=self.dtype)(x))
        x = nn.relu(nn.Conv(64, (3, 3), strides=(1, 1), padding="VALID", dtype=self.dtype)(x))
        x = jnp.reshape(x, (x.shape[0], -1))
        x = nn.relu(nn.Dense(self.features_dim, dtype=self.dtype)(x))
        return jnp.reshape(x, lead + (self.features_dim,))


class LayerNormGRUCell(nn.Module):
    """Hafner-style LN-GRU cell (reference models.py:331-410).

    One fused matmul of concat([x, h]) against a [D+H, 3H] kernel → LN →
    split(reset, cand, update); ``update = σ(u - 1)`` bias trick (:399-403).
    Carries hidden state explicitly so it drops straight into `lax.scan`.
    """

    hidden_size: int
    use_bias: bool = False
    layer_norm: bool = True
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, h: jax.Array, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        inp = jnp.concatenate([x, h], axis=-1)
        y = HoistableDense(3 * self.hidden_size, use_bias=self.use_bias, dtype=self.dtype, name="fused")(inp)
        if self.layer_norm:
            y = LayerNorm(eps=1e-3)(y)
        reset, cand, update = jnp.split(y, 3, axis=-1)
        reset = nn.sigmoid(reset)
        cand = jnp.tanh(reset * cand)
        update = nn.sigmoid(update - 1.0)
        new_h = update * cand + (1.0 - update) * h
        return new_h, new_h


class MultiEncoder(nn.Module):
    """Dict-observation fusion encoder (reference models.py:413-455).

    `cnn_encoder` consumes the channel-concatenated image keys, `mlp_encoder`
    the concatenated vector keys; outputs are concatenated on the feature
    axis. Either may be None.
    """

    cnn_encoder: Optional[nn.Module]
    mlp_encoder: Optional[nn.Module]

    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        feats = []
        if self.cnn_encoder is not None:
            feats.append(self.cnn_encoder(obs))
        if self.mlp_encoder is not None:
            feats.append(self.mlp_encoder(obs))
        return jnp.concatenate(feats, axis=-1)


class MultiDecoder(nn.Module):
    """Dict-observation decoder (reference models.py:458-504): returns the
    union of the cnn and mlp decoders' reconstruction dicts."""

    cnn_decoder: Optional[nn.Module]
    mlp_decoder: Optional[nn.Module]

    def __call__(self, features: jax.Array) -> Dict[str, jax.Array]:
        out: Dict[str, jax.Array] = {}
        if self.cnn_decoder is not None:
            out.update(self.cnn_decoder(features))
        if self.mlp_decoder is not None:
            out.update(self.mlp_decoder(features))
        return out


def hafner_uniform_init(scale: float = 1.0):
    """DreamerV3 'Hafner' trunc-normal-free init: uniform over fan-avg
    (reference dreamer_v3/agent.py:1170-1180 uses xavier-uniform-like init)."""

    def init(key, shape, dtype=jnp.float32):
        fan_in = np.prod(shape[:-1]) if len(shape) > 1 else shape[0]
        fan_out = shape[-1]
        limit = float(np.sqrt(6.0 * scale / (fan_in + fan_out)))
        return jax.random.uniform(key, shape, dtype, -limit, limit)

    return init


def orthogonal_init(scale: float = np.sqrt(2)):
    return nn.initializers.orthogonal(scale)
