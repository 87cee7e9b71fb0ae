"""Plain float32 reference of PPO's first update (Schulman et al. 2017, as the
sheeprl recipe `exp=ppo` configures it) on one rollout: the actor-critic's
forward over the rollout's observations (an MLP encoder over the vector keys,
a tanh trunk each for actor and critic, one categorical head), generalized
advantage estimation, and `epochs` x `minibatches` steps of Adam on the
clipped surrogate, the value loss and the entropy bonus.

Straightforward ``jax.numpy``: no donation, everything held and summed in
float32, every matmul at ``Precision.HIGHEST``. It imports nothing of the
program. What it shares with the program is the *names* of the weight leaves
(so that the same seeded weights go to both) and the way the update's PRNG
key is split into one permutation of the rollout's rows per epoch (so that
both take the same minibatches).

It recomputes what the player stored: the values and the log-probabilities of
the taken actions come from the seeded weights, the advantages from those
values, so the whole chain from observation to parameters is followed.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..reference import HI, adam_init, adam_update, clip_global, flatten, nest


class Sizes(NamedTuple):
    """What the reference needs of a cell; the adapter's `sizes_for` fills it
    from the composed config the program runs with."""
    keys: Tuple[str, ...]
    envs: int
    steps: int
    gamma: float
    gae_lambda: float
    epochs: int
    minibatches: int
    minibatch_rows: int
    normalize_advantages: bool
    clip_vloss: bool
    lr: float
    eps: float
    max_grad_norm: float


def dense(p, x):
    return jnp.dot(x, p["kernel"], precision=HI) + p["bias"]


def mlp(p, x):
    i = 0
    while f"dense_{i}" in p:
        x = jnp.tanh(dense(p[f"dense_{i}"], x))
        i += 1
    return dense(p["out"], x) if "out" in p else x


def forward(params, obs: Dict[str, jax.Array], sz: Sizes):
    """(logits [rows, actions], values [rows, 1])."""
    feat = mlp(params["encoder"]["MLP_0"], jnp.concatenate([obs[k].astype(jnp.float32) for k in sz.keys], -1))
    return dense(params["actor_heads_0"], mlp(params["actor_backbone"], feat)), mlp(params["critic"], feat)


def log_probs_and_entropy(logits, actions):
    lp = jax.nn.log_softmax(logits, -1)
    taken = jnp.take_along_axis(lp, actions.astype(jnp.int32), -1)
    return taken, -jnp.sum(jnp.exp(lp) * lp, -1, keepdims=True)


def gae(rewards, values, dones, next_value, sz: Sizes):
    """[steps, envs, 1] each; `dones[t]` marks an episode that ended AT step t."""
    adv = jnp.zeros_like(next_value)
    out = []
    for t in reversed(range(sz.steps)):
        nxt = next_value if t == sz.steps - 1 else values[t + 1]
        delta = rewards[t] + sz.gamma * nxt * (1.0 - dones[t]) - values[t]
        adv = delta + sz.gamma * sz.gae_lambda * (1.0 - dones[t]) * adv
        out.append(adv)
    return jnp.stack(out[::-1])


def loss(params, mb, coefs, sz: Sizes):
    logits, values = forward(params, mb["obs"], sz)
    logprobs, entropy = log_probs_and_entropy(logits, mb["actions"])
    adv = mb["advantages"]
    if sz.normalize_advantages:
        adv = (adv - jnp.mean(adv)) / (jnp.std(adv) + 1e-8)
    ratio = jnp.exp(logprobs - mb["logprobs"])
    clip = coefs["clip_coef"]
    policy = jnp.mean(jnp.maximum(-adv * ratio, -adv * jnp.clip(ratio, 1.0 - clip, 1.0 + clip)))
    value = jnp.square(values - mb["returns"])
    if sz.clip_vloss:
        clipped = mb["values"] + jnp.clip(values - mb["values"], -clip, clip)
        value = jnp.maximum(value, jnp.square(clipped - mb["returns"]))
    value = 0.5 * jnp.mean(value)
    ent = -jnp.mean(entropy)
    total = policy + coefs["vf_coef"] * value + coefs["ent_coef"] * ent
    return total, {"Loss/policy_loss": policy, "Loss/value_loss": value, "Loss/entropy_loss": ent}


def first_update(weights: Dict[str, jax.Array], rollout: Dict[str, np.ndarray], next_obs: Dict[str, np.ndarray],
                 coefs: Dict[str, float], key_data: np.ndarray, sz: Sizes,
                 params_after: Optional[Dict[str, np.ndarray]] = None) -> Dict[str, Any]:
    """The first update on the rollout `rollout` (rows `t * envs + e`), from
    the seeded `weights`: the values, log-probabilities and advantages the
    rollout should hold, the losses (means over the gradient steps, as the
    program reports them), the leaf norms of the parameters' change and, with
    `params_after`, of the program's change against the same weights."""
    params = nest(dict(weights))
    obs = {k: jnp.asarray(rollout[f"obs:{k}"]) for k in sz.keys}
    actions = jnp.asarray(rollout["actions"])
    logits, values = forward(params, obs, sz)
    logprobs, _ = log_probs_and_entropy(logits, actions)
    _, next_value = forward(params, {k: jnp.asarray(v) for k, v in next_obs.items()}, sz)
    shape = (sz.steps, sz.envs, 1)
    adv = gae(jnp.asarray(rollout["rewards"]).reshape(shape), values.reshape(shape), jnp.asarray(rollout["dones"]).reshape(shape),
              next_value, sz).reshape(-1, 1)
    data = {"obs": obs, "actions": actions, "logprobs": logprobs, "values": values, "advantages": adv, "returns": adv + values}

    step = jax.jit(jax.value_and_grad(lambda p, mb: loss(p, mb, coefs, sz), has_aux=True))
    opt = adam_init(params)
    key = jnp.asarray(key_data, jnp.uint32)
    auxs = []
    for _ in range(sz.epochs):
        key, pk = jax.random.split(key)
        perm = jax.random.permutation(pk, sz.steps * sz.envs)
        for idx in perm[: sz.minibatches * sz.minibatch_rows].reshape(sz.minibatches, sz.minibatch_rows):
            (_, aux), grads = step(params, jax.tree.map(lambda x: x[idx], data))
            if sz.max_grad_norm > 0:
                grads = clip_global(grads, sz.max_grad_norm)
            params, opt = adam_update(params, grads, opt, sz.lr * coefs["lr_frac"], sz.eps)
            auxs.append(aux)

    def delta_norms(after: Dict[str, Any]) -> Dict[str, float]:
        return {k: float(jnp.sqrt(jnp.sum(jnp.square(jnp.asarray(after[k]) - weights[k])))) for k in weights}

    out = {
        "values": np.asarray(values), "logprobs": np.asarray(logprobs), "advantages": np.asarray(adv),
        "losses": {k: float(np.mean([float(a[k]) for a in auxs])) for k in auxs[0]},
        "delta": delta_norms(flatten(params)),
    }
    if params_after is not None:
        out["program_delta"] = delta_norms(params_after)
    return out
