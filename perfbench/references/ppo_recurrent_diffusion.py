"""Plain float32 reference of a block-diffusion policy's first update on the
recurrent on-policy loop (`exp=ppo_recurrent_sdar`): one full forward over
the concatenated layout of a rollout (the clean sequence of observed blocks,
then every denoising copy of every action block) under the explicit square
mask, the clipped-surrogate, value and entropy losses, their gradients and
Adam. Straightforward ``jax.numpy`` at ``Precision.HIGHEST``: no cache, no
two-part attention (the whole `(1 + S) T L` square of scores, masked), no
grouping (every held expert on every token, masked by the routing), no remat.
It imports nothing of the program; what it shares with it are the NAMES of
the weight leaves and the way the update's key is split into one permutation
of the sequences per epoch.

It holds the same share of each layer as the program (`first_expert`,
`experts_held`; the vocabulary slice) and leaves out what the absent chips
would add: the other experts' outputs and the rest of the vocabulary.

Equations, per token with state x in R^C:

Attention (grouped-query). u = RMSNorm_g1(x); q_h = RMSNorm_gq(W_q^h u),
h = 1..H; k_j = RMSNorm_gk(W_k^j u), v_j = W_v^j u, j = 1..KV; q and k
rotated at the token's position by RoPE with theta, the two halves of each
head (not interleaved pairs); a_h = softmax over the keys the mask allows of
q_h . k_{h // (H / KV)} / sqrt(D), applied to v of the same KV head;
x' = x + W_o [a_1 .. a_H].

Experts. u' = RMSNorm_g2(x'); p = softmax(W_r u') over all routed experts;
S = the top-k of p; w_e = p_e / sum over S of p; y = sum over the experts of S
HELD HERE of w_e W_down^e(silu(W_gate^e u') * W_up^e u'); x'' = x' + y.

Head. Logits = RMSNorm_g(x) W_head over the held slice, the mask id's -1e30
(never an answer); the value is one linear output on the same normed state.

The layout and the mask. Observed block t lies at positions L t .. L t + L - 1
of the context, action block t is denoised at L (t + 1) ..; copy s of action
block t holds the ids whose `order` is under s and the mask id elsewhere. A
clean token of block t sees the clean tokens of blocks 0..t of its episode; a
token of copy (t, s) sees those same clean tokens and the L tokens of copy
(t, s). A block's log-probability is the sum over its positions of the
log-probability of the action's id in the copy that committed it (copy
`order`), its entropy the sum of those copies' entropies; the value is read
at the clean block's last position.

``od`` (operand dtype) rounds every matmul operand to that type before the
float32 product: None is the reference, float8_e4m3fn the control that has to
come out as not correct.
"""
from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..reference import HI, flatten, nest

NEG = -1e30


class Sizes(NamedTuple):
    """What the reference needs of a cell; the adapter's `sizes_for` fills it from the composed config."""
    heads: int
    kv_heads: int
    head_dim: int
    experts: int  # routed over
    top_k: int
    experts_held: int
    first_expert: int
    layers: int
    theta: float
    norm_eps: float
    block: int  # L
    steps: int  # S, denoising steps
    mask_id: int
    envs: int
    blocks: int  # T
    gamma: float
    gae_lambda: float
    epochs: int
    minibatches: int
    minibatch_seqs: int
    normalize_advantages: bool
    clip_vloss: bool
    lr: float
    eps: float
    max_grad_norm: float


def _q(x, od):
    if od is None:
        return x
    m = float(jnp.finfo(od).max)
    return jnp.clip(x, -m, m).astype(od).astype(jnp.float32)


def mm(x, w, od):
    return jnp.matmul(_q(x, od), _q(w, od), precision=HI)


def rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def rotate(x, positions, theta):
    """x [B, Q, heads, D] rotated at positions [Q]: the two halves of D by position x frequency."""
    d = x.shape[-1]
    freq = (theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)).astype(np.float32)
    angle = (positions[:, None].astype(jnp.float32) * freq)[:, None]  # [Q, 1, D/2]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle), b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def layout(tokens, actions, order, is_first, sz: Sizes):
    """(ids [B, Q], positions [Q], mask [B, Q, Q]) of the concatenated layout: Q = T L clean rows, then (t, s, i)."""
    B, T, L = tokens.shape
    S = sz.steps
    copies = jnp.where(order[:, :, None, :] < jnp.arange(S)[None, None, :, None], actions[:, :, None, :], sz.mask_id)
    ids = jnp.concatenate([tokens.reshape(B, -1), copies.reshape(B, -1)], 1)
    t, s, i = np.meshgrid(np.arange(T), np.arange(S), np.arange(L), indexing="ij")
    block = np.concatenate([np.repeat(np.arange(T), L), t.reshape(-1)])
    copy = np.concatenate([np.full(T * L, -1), (t * S + s).reshape(-1)])  # -1: a clean row
    positions = jnp.asarray(np.concatenate([np.arange(T * L), (L * (t + 1) + i).reshape(-1)]))
    episode = jnp.cumsum(is_first.astype(jnp.int32), 1)[:, block]  # [B, Q]
    sees_clean = (copy[None, :] < 0) & (block[None, :] <= block[:, None])  # [Q (query), Q (key)]
    same_copy = (copy[:, None] >= 0) & (copy[:, None] == copy[None, :])
    mask = (jnp.asarray(sees_clean)[None] & (episode[:, :, None] == episode[:, None, :])) | jnp.asarray(same_copy)[None]
    return ids, positions, mask


def attention(p, u, positions, mask, sz: Sizes, od):
    B, Q, _ = u.shape
    H, KV, D = sz.heads, sz.kv_heads, sz.head_dim
    q = rotate(rms(mm(u, p["w_q"]["kernel"], od).reshape(B, Q, H, D), p["q_norm"]["scale"], sz.norm_eps), positions, sz.theta)
    k = rotate(rms(mm(u, p["w_k"]["kernel"], od).reshape(B, Q, KV, D), p["k_norm"]["scale"], sz.norm_eps), positions, sz.theta)
    v = mm(u, p["w_v"]["kernel"], od).reshape(B, Q, KV, D)
    group = np.arange(H) // (H // KV)
    s = jnp.einsum("bqhd,bkhd->bhqk", _q(q, od), _q(k[:, :, group], od), precision=HI) / math.sqrt(D)
    a = jax.nn.softmax(jnp.where(mask[:, None], s, NEG), -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _q(a, od), _q(v[:, :, group], od), precision=HI)
    return mm(o.reshape(B, Q, H * D), p["w_o"]["kernel"], od)


def gated(p, u, od):
    return mm(jax.nn.silu(mm(u, p["w_gate"]["kernel"], od)) * mm(u, p["w_up"]["kernel"], od), p["w_down"]["kernel"], od)


def experts(p, u, sz: Sizes, od):
    """(output, the chosen experts [.., k] sorted): every held expert on every token, masked by the routing."""
    prob = jax.nn.softmax(mm(u, p["router"]["kernel"], od), -1)
    top, idx = jax.lax.top_k(prob, sz.top_k)
    w = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx, sz.experts) * w[..., None], -2)  # 0 where not chosen
    y = jnp.zeros_like(u)
    for e in range(sz.experts_held):
        y = y + gate[..., sz.first_expert + e, None] * gated(jax.tree.map(lambda x: x[e], p["experts"]), u, od)
    return y, jnp.sort(idx, -1)


def layer(p, X, positions, mask, sz: Sizes, od):
    X = X + attention(p["attn"], rms(X, p["attn_norm"]["scale"], sz.norm_eps), positions, mask, sz, od)
    return X + experts(p["moe"], rms(X, p["ffn_norm"]["scale"], sz.norm_eps), sz, od)[0]


def readout(params, X, actions, order, sz: Sizes, od):
    """(log-probabilities [B, T] of the action blocks, entropies [B, T], values [B, T]) of the final states X."""
    B, T, L = actions.shape
    x = rms(X, params["final_norm"]["scale"], sz.norm_eps)
    values = mm(x[:, L - 1:T * L:L], params["value"]["kernel"], od)[..., 0] + params["value"]["bias"][0]
    copies = x[:, T * L:].reshape(B, T, sz.steps, L, -1)
    picked = jnp.take_along_axis(copies, order[:, :, None, :, None], 2)[:, :, 0]  # the copy that committed each position
    logits = mm(picked, params["head"]["kernel"], od)
    logits = jnp.where(jnp.arange(logits.shape[-1]) == sz.mask_id, NEG, logits)
    logp = jax.nn.log_softmax(logits, -1)
    taken = jnp.sum(jnp.take_along_axis(logp, actions[..., None], -1)[..., 0], -1)
    entropy = -jnp.sum(jnp.exp(logp) * logp, (-2, -1))
    return taken, entropy, values


def forward(params, tokens, actions, order, is_first, sz: Sizes, od=None):
    """(log-probabilities [B, T], entropies [B, T], values [B, T]) of a batch of sequences of blocks."""
    ids, positions, mask = layout(tokens, actions, order, is_first, sz)
    X = params["embed"]["embedding"][ids]
    for i in range(sz.layers):
        X = layer(params[f"layer_{i}"], X, positions, mask, sz, od)
    return readout(params, X, actions, order, sz, od)


def losses_of(taken, entropy, values, mb, coefs, sz: Sizes):
    adv = mb["advantages"]
    if sz.normalize_advantages:
        adv = (adv - jnp.mean(adv)) / (jnp.std(adv) + 1e-8)
    ratio, clip = jnp.exp(taken - mb["logprobs"]), coefs["clip_coef"]
    policy = jnp.mean(jnp.maximum(-adv * ratio, -adv * jnp.clip(ratio, 1.0 - clip, 1.0 + clip)))
    value = jnp.square(values - mb["returns"])
    if sz.clip_vloss:
        value = jnp.maximum(value, jnp.square(mb["values"] + jnp.clip(values - mb["values"], -clip, clip) - mb["returns"]))
    value, ent = 0.5 * jnp.mean(value), -jnp.mean(entropy)
    return policy + coefs["vf_coef"] * value + coefs["ent_coef"] * ent, {
        "Loss/policy_loss": policy, "Loss/value_loss": value, "Loss/entropy_loss": ent}


def loss(params, mb, coefs, sz: Sizes, od=None):
    """The whole loss of one minibatch as one function: what `grads_by_layer` has to agree with."""
    return losses_of(*forward(params, mb["tokens"], mb["actions"], mb["order"], mb["is_first"], sz, od), mb, coefs, sz)


def gae(rewards, values, dones, sz: Sizes):
    """[T, envs] each; `dones[t]` marks an episode that ended AT step t; the rollout's last step always does."""
    adv, out = jnp.zeros_like(values[0]), []
    for t in reversed(range(sz.blocks)):
        nxt = jnp.zeros_like(values[0]) if t == sz.blocks - 1 else values[t + 1]
        delta = rewards[t] + sz.gamma * nxt * (1.0 - dones[t]) - values[t]
        adv = delta + sz.gamma * sz.gae_lambda * (1.0 - dones[t]) * adv
        out.append(adv)
    return jnp.stack(out[::-1])


# -- in blocks: a few sequences at a time, a layer at a time (what fits beside the weights and Adam) --------
@lru_cache(maxsize=None)
def _pieces(sz: Sizes, od):
    """The jitted pieces, made once per (the model's sizes, operand type)."""

    def start(params, mb):
        ids, positions, mask = layout(mb["tokens"], mb["actions"], mb["order"], mb["is_first"], sz)
        return params["embed"]["embedding"][ids], positions, mask

    def top(rest, X, mb, coefs):
        return losses_of(*readout(rest, X, mb["actions"], mb["order"], sz, od), mb, coefs, sz)

    def run_layer(p, X, positions, mask):
        return layer(p, X, positions, mask, sz, od)

    return {
        "forward": jax.jit(lambda p, t, a, o, f: forward(p, t, a, o, f, sz, od)), "start": jax.jit(start), "layer": jax.jit(run_layer),
        "whole": jax.jit(jax.value_and_grad(lambda p, mb, coefs: loss(p, mb, coefs, sz, od), has_aux=True)),
        "top": jax.jit(jax.value_and_grad(top, argnums=(0, 1), has_aux=True)),
        "back": jax.jit(lambda p, X, positions, mask, ct: jax.vjp(lambda p_, X_: run_layer(p_, X_, positions, mask), p, X)[1](ct)),
    }


def _programs(sz: Sizes, od):
    return _pieces(sz._replace(envs=0, epochs=0, minibatches=0, minibatch_seqs=0), od)


def rollout_forward(params, rollout: Dict[str, np.ndarray], sz: Sizes, od=None, block: int = 4):
    """`forward` over all sequences [envs, T, ...], `block` of them at a time: (log-probabilities [envs, T] of the
    rollout's action blocks, values [envs, T])."""
    run = _programs(sz, od)["forward"]
    parts = [run(params, *(jnp.asarray(rollout[k][i: i + block]) for k in ("tokens", "actions", "order", "is_first")))
             for i in range(0, rollout["tokens"].shape[0], block)]
    return jnp.concatenate([x[0] for x in parts]), jnp.concatenate([x[2] for x in parts])


def grads_by_layer(params, mb, coefs, sz: Sizes, od=None):
    """The gradient of `loss` a layer at a time: forward keeping each layer's input, then each layer's vjp from the
    head back. The same numbers as `jax.grad(loss)`, with one layer's intermediates alive at a time."""
    run = _programs(sz, od)
    X, positions, mask = run["start"](params, mb)
    inputs = [X]
    for i in range(sz.layers):
        inputs.append(run["layer"](params[f"layer_{i}"], inputs[-1], positions, mask))
    rest = {k: v for k, v in params.items() if not k.startswith("layer_") and k != "embed"}
    (_, aux), (g_rest, dX) = run["top"](rest, inputs.pop(), mb, coefs)
    grads = dict(g_rest)
    for i in reversed(range(sz.layers)):
        grads[f"layer_{i}"], dX = run["back"](params[f"layer_{i}"], inputs.pop(), positions, mask, dX)
    ids = layout(mb["tokens"], mb["actions"], mb["order"], mb["is_first"], sz)[0]
    grads["embed"] = {"embedding": jnp.zeros_like(params["embed"]["embedding"]).at[ids].add(dX)}
    return aux, grads


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam_leaf(p, m, v, g, scale, lr, eps, c1, c2):
    g = g * scale
    m, v = 0.9 * m + 0.1 * g, 0.999 * v + 0.001 * jnp.square(g)
    return p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps), m, v


def first_update(weights: Dict[str, jax.Array], data: Dict[str, np.ndarray], coefs: Dict[str, float], key_data: np.ndarray,
                 sz: Sizes, od=None, whole: bool = False) -> Tuple[Dict[str, jax.Array], List[Dict[str, float]]]:
    """`epochs` x `minibatches` Adam steps on `data` (sequence-major: tokens, actions, order [envs, T, L]; is_first,
    logprobs, values, returns, advantages [envs, T]) from the seeded `weights`, WHICH ARE CONSUMED (every leaf is
    donated): (the parameters after, flat; the losses of every step). `whole` takes each gradient by `jax.grad` of
    the whole loss instead of a layer at a time (small sizes)."""
    flat = dict(weights)
    m, v = {k: jnp.zeros_like(x) for k, x in flat.items()}, {k: jnp.zeros_like(x) for k, x in flat.items()}
    data = {k: jnp.asarray(x) for k, x in data.items()}
    coefs = {k: jnp.float32(x) for k, x in coefs.items()}
    key = jnp.asarray(key_data, jnp.uint32)
    count, steps = 0, []
    for _ in range(sz.epochs):
        key, pk = jax.random.split(key)
        perm = jax.random.permutation(pk, sz.envs)
        for idx in perm[: sz.minibatches * sz.minibatch_seqs].reshape(sz.minibatches, sz.minibatch_seqs):
            mb = {k: x[idx] for k, x in data.items()}
            params = nest(flat)
            if whole:
                (_, aux), grads = _programs(sz, od)["whole"](params, mb, coefs)
            else:
                aux, grads = grads_by_layer(params, mb, coefs, sz, od)
            count += 1
            g = flatten(grads)
            scale = 1.0
            if sz.max_grad_norm > 0:
                norm = math.sqrt(sum(float(jnp.sum(jnp.square(x))) for x in g.values()))
                scale = 1.0 if norm < sz.max_grad_norm else sz.max_grad_norm / norm
            c1, c2, lr = 1 - 0.9 ** count, 1 - 0.999 ** count, sz.lr * float(coefs["lr_frac"])
            for k in flat:
                flat[k], m[k], v[k] = _adam_leaf(flat[k], m[k], v[k], g[k], scale, lr, sz.eps, c1, c2)
            steps.append({k: float(x) for k, x in aux.items()})
    return flat, steps
