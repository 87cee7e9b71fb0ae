"""Plain float32 reference of a sequence policy's first update on the recurrent
on-policy loop (`exp=ppo_recurrent_xing4`): the full forward over a rollout
without any cache, the clipped-surrogate, value and entropy losses, their
gradients and Adam. Straightforward ``jax.numpy`` at ``Precision.HIGHEST``: no
cache (every token attends over the expanded keys and values of its episode's
earlier tokens), no grouping (every held expert is evaluated on every token
and masked by the routing), no remat. It imports nothing of the program; what
it shares with it are the NAMES of the weight leaves and the way the update's
key is split into one permutation of the sequences per epoch.

It is given the same share of each layer as the program (`first_expert`,
`experts_held`, `heads_held`, `vocab_held`) and, like it, leaves out what the
absent chips would add: the other heads' part of W_o's sum, the other experts'
outputs, the rest of the vocabulary.

Equations, per token with hidden state u in R^C (streams X in R^{n x C}):

Hyper-connections (mHC, arXiv 2512.24880; n = hc_mult). Around each sublayer F
(attention, feed-forward): x~ = RMSNorm(vec(X)) in R^{nC}, without gain;
  H~pre = a_pre (x~ Phi_pre) + b_pre in R^n,  H~post = a_post (x~ Phi_post) + b_post in R^n,
  H~res = a_res mat(x~ Phi_res) + B_res in R^{n x n};
  H_pre = sigmoid(H~pre), H_post = 2 sigmoid(H~post), H_res = SK(clip(H~res, min, max)) with M = exp(.) and
  hc_sinkhorn_iters rounds of rows M / (rowsum + hc_eps), then columns M / (colsum + hc_eps);
  y = F(RMSNorm_g(H_pre X));  X' = H_res X + H_post^T y.
The embedding is copied into the n streams; after the last layer the streams are summed, then the final RMSNorm.

Latent attention (MLA). c_q = RMSNorm_g(u W_dq); [q_nope | q_rope] = c_q W_uq per head;
[c_kv | k_r] = u W_dkv, c_kv = RMSNorm_g(c_kv), k_rope = RoPE(k_r) shared by the heads; [k_nope | v] = c_kv W_ukv
per head; score (q_nope . k_nope + RoPE(q_rope) . k_rope) * (nope + rope)^-0.5 * m^2, m = 0.1 mscale_all_dim
ln(factor) + 1; the rotary frequencies are blended (YaRN) between the trained ones and those over `factor` by a
linear ramp between the dimensions that turn beta_fast and beta_slow times over the original context; causal and
same-episode mask; heads concatenated through W_o.

Experts. s = sigmoid(u W_r) over all n_routed_experts; top-k of s + e_bias (e_bias gets no gradient); weights
s_k / (sum s_k + 1e-20) * routed_scaling_factor; y = sum over the chosen experts HELD HERE of w_k E_k(u), plus
E_shared(u); E(u) = W_down(silu(W_gate u) * W_up u).

Head. Logits = RMSNorm_g(sum of streams) W_head over the held slice; the value is one linear output on the same
normalised state.

Assumed where the published configuration does not settle it (also in the configuration's file): streams copied in
and summed out; x~ without gain; rotary halves rotated (not interleaved pairs); position = index in the rollout;
e_bias a seeded constant; the value head.

``od`` (operand dtype) rounds every matmul operand to that type before the float32 product: None is the reference,
float8_e4m3fn the control that has to come out as not correct.
"""
from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..reference import HI, flatten, nest


class Sizes(NamedTuple):
    """What the reference needs of a cell; the adapter's `sizes_for` fills it from the composed config."""
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    heads: int  # held
    experts: int  # routed over
    top_k: int
    experts_held: int
    first_expert: int
    scaling: float
    layers: int
    streams: int
    sinkhorn: int
    hc_eps: float
    clamp: Tuple[float, float]
    theta: float
    factor: float
    beta_fast: float
    beta_slow: float
    mscale_all_dim: float
    original_context: int
    norm_eps: float
    envs: int
    steps: int
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


def ein(spec, a, b, od):
    return jnp.einsum(spec, _q(a, od), _q(b, od), precision=HI)


def rms(x, scale, eps):
    y = x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y if scale is None else y * scale


def rotary_frequencies(sz: Sizes) -> np.ndarray:
    d = sz.rope
    trained = sz.theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if sz.factor <= 1:
        return trained.astype(np.float32)
    dim_of = lambda turns: d * math.log(sz.original_context / (turns * 2 * math.pi)) / (2 * math.log(sz.theta))  # noqa: E731
    low, high = max(math.floor(dim_of(sz.beta_fast)), 0), min(math.ceil(dim_of(sz.beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (trained * (1 - ramp) + trained / sz.factor * ramp).astype(np.float32)


def rotate(x, positions, freq):
    """x [..., T, (H,) d] with positions [T] on the T axis: the two halves of d rotated by position x frequency."""
    angle = positions[:, None].astype(jnp.float32) * freq  # [T, d/2]
    if x.ndim == 4:
        angle = angle[:, None]
    a, b = x[..., : x.shape[-1] // 2], x[..., x.shape[-1] // 2:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle), b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def hyper(p, X, sz: Sizes, od):
    """(H_pre [B, T, n], H_post [B, T, n], H_res [B, T, n, n]) from the streams X [B, T, n, C]."""
    n = sz.streams
    x = rms(X.reshape(*X.shape[:2], -1), None, sz.norm_eps)
    pre = jax.nn.sigmoid(p["a_pre"] * mm(x, p["phi_pre"]["kernel"], od) + p["b_pre"])
    post = 2.0 * jax.nn.sigmoid(p["a_post"] * mm(x, p["phi_post"]["kernel"], od) + p["b_post"])
    res = p["a_res"] * mm(x, p["phi_res"]["kernel"], od).reshape(*X.shape[:2], n, n) + p["b_res"]
    M = jnp.exp(jnp.clip(res, sz.clamp[0], sz.clamp[1]))
    for _ in range(sz.sinkhorn):
        M = M / (jnp.sum(M, -1, keepdims=True) + sz.hc_eps)
        M = M / (jnp.sum(M, -2, keepdims=True) + sz.hc_eps)
    return pre, post, M


def around(p, X, F, sz: Sizes, od):
    pre, post, res = hyper(p, X, sz, od)
    y = F(rms(jnp.einsum("btn,btnc->btc", pre, X, precision=HI), p["norm"]["scale"], sz.norm_eps))
    return jnp.einsum("btij,btjc->btic", res, X, precision=HI) + post[..., None] * y[:, :, None, :]


def attention(p, u, mask, sz: Sizes, od):
    B, T, _ = u.shape
    positions, freq = jnp.arange(T), rotary_frequencies(sz)
    cq = rms(mm(u, p["w_dq"]["kernel"], od), p["q_norm"]["scale"], sz.norm_eps)
    q = mm(cq, p["w_uq"]["kernel"], od).reshape(B, T, sz.heads, sz.nope + sz.rope)
    q = jnp.concatenate([q[..., : sz.nope], rotate(q[..., sz.nope:], positions, freq)], -1)
    ckv = mm(u, p["w_dkv"]["kernel"], od)
    c = rms(ckv[..., : sz.kv_rank], p["kv_norm"]["scale"], sz.norm_eps)
    k_rope = rotate(ckv[..., sz.kv_rank:], positions, freq)
    kv = mm(c, p["w_ukv"]["kernel"], od).reshape(B, T, sz.heads, sz.nope + sz.v_dim)
    k = jnp.concatenate([kv[..., : sz.nope], jnp.broadcast_to(k_rope[:, :, None], (B, T, sz.heads, sz.rope))], -1)
    m = 0.1 * sz.mscale_all_dim * math.log(sz.factor) + 1.0 if sz.factor > 1 else 1.0
    s = ein("bthd,bshd->bhts", q, k, od) * ((sz.nope + sz.rope) ** -0.5 * m * m)
    a = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), -1)
    return mm(ein("bhts,bshd->bthd", a, kv[..., sz.nope:], od).reshape(B, T, -1), p["w_o"]["kernel"], od)


def gated(p, u, od):
    return mm(jax.nn.silu(mm(u, p["w_gate"]["kernel"], od)) * mm(u, p["w_up"]["kernel"], od), p["w_down"]["kernel"], od)


def experts(p, u, sz: Sizes, od):
    """(output, the chosen experts [B, T, k] sorted): every held expert on every token, masked by the routing."""
    s = jax.nn.sigmoid(mm(u, p["router"]["kernel"], od))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(p["e_bias"]), sz.top_k)
    sk = jnp.take_along_axis(s, idx, -1)
    w = sk / (jnp.sum(sk, -1, keepdims=True) + 1e-20) * sz.scaling
    gate = jnp.sum(jax.nn.one_hot(idx, sz.experts) * w[..., None], -2)  # [B, T, experts]: 0 where not chosen
    y = gated(p["shared"], u, od)
    for e in range(sz.experts_held):
        one = jax.tree.map(lambda x: x[e], p["experts"])
        y = y + gate[..., sz.first_expert + e, None] * gated(one, u, od)
    return y, jnp.sort(idx, -1)


def layer(p, X, mask, sz: Sizes, od):
    """One layer over the streams X [B, T, n, C]: (X', the expert choices or None)."""
    X = around(p["attn_hc"], X, lambda u: attention(p["attn"], u, mask, sz, od), sz, od)
    if "mlp" in p:
        return around(p["ffn_hc"], X, lambda u: gated(p["mlp"], u, od), sz, od), None
    chosen = []

    def ffn(u):
        y, idx = experts(p["moe"], u, sz, od)
        chosen.append(idx)
        return y

    return around(p["ffn_hc"], X, ffn, sz, od), chosen[0]


def episode_mask(is_first):
    """[B, T, T]: token t may look at token s of its own episode, s <= t."""
    episode = jnp.cumsum(is_first.astype(jnp.int32), 1)
    t = jnp.arange(is_first.shape[1])
    return (episode[:, :, None] == episode[:, None, :]) & (t[:, None] >= t[None, :])


def embed(params, tokens, sz: Sizes):
    return jnp.repeat(params["embed"]["embedding"][tokens][:, :, None], sz.streams, 2)


def head(params, X, sz: Sizes, od):
    x = rms(jnp.sum(X, 2), params["final_norm"]["scale"], sz.norm_eps)
    return mm(x, params["head"]["kernel"], od), mm(x, params["value"]["kernel"], od)[..., 0] + params["value"]["bias"][0]


def forward(params, tokens, is_first, sz: Sizes, od=None):
    """(logits [B, T, vocab held], values [B, T], the expert choices [expert layers, B, T, k])."""
    mask = episode_mask(is_first)
    X = embed(params, tokens, sz)
    chosen = []
    for i in range(sz.layers):
        X, idx = layer(params[f"layer_{i}"], X, mask, sz, od)
        if idx is not None:
            chosen.append(idx)
    logits, values = head(params, X, sz, od)
    return logits, values, jnp.stack(chosen) if chosen else jnp.zeros((0, *tokens.shape, sz.top_k), jnp.int32)


def losses_of(logits, values, mb, coefs, sz: Sizes):
    logp = jax.nn.log_softmax(logits, -1)
    taken = jnp.take_along_axis(logp, mb["actions"][..., None], -1)[..., 0]
    entropy = -jnp.sum(jnp.exp(logp) * logp, -1)
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
    logits, values, _ = forward(params, mb["tokens"], mb["is_first"], sz, od)
    return losses_of(logits, values, mb, coefs, sz)


def gae(rewards, values, dones, sz: Sizes):
    """[T, envs] each; `dones[t]` marks an episode that ended AT step t; the rollout's last step always does."""
    adv, out = jnp.zeros_like(values[0]), []
    for t in reversed(range(sz.steps)):
        nxt = jnp.zeros_like(values[0]) if t == sz.steps - 1 else values[t + 1]
        delta = rewards[t] + sz.gamma * nxt * (1.0 - dones[t]) - values[t]
        adv = delta + sz.gamma * sz.gae_lambda * (1.0 - dones[t]) * adv
        out.append(adv)
    return jnp.stack(out[::-1])


# -- in blocks: a minibatch at a time, a layer at a time (what fits beside 656 M float32 weights and Adam) --------
def _programs(sz: Sizes, od):
    """The jitted pieces, made once per (the model's sizes, operand type): a second call must not compile again,
    nor does an update cut into other minibatches need other pieces."""
    return _pieces(sz._replace(epochs=0, minibatches=0, minibatch_seqs=0), od)


@lru_cache(maxsize=None)
def _pieces(sz: Sizes, od):

    def block(params, tokens, is_first, actions):
        logits, values, chosen = forward(params, tokens, is_first, sz, od)
        return jnp.take_along_axis(jax.nn.log_softmax(logits, -1), actions[..., None], -1)[..., 0], values, chosen

    def top(rest, X, mb, coefs):
        logits, values = head(rest, X, sz, od)
        return losses_of(logits, values, mb, coefs, sz)

    def run_layer(p, X, mask):
        return layer(p, X, mask, sz, od)[0]

    return {
        "block": jax.jit(block), "embed": jax.jit(partial(embed, sz=sz)), "layer": jax.jit(run_layer),
        "whole": jax.jit(jax.value_and_grad(lambda p, mb, coefs: loss(p, mb, coefs, sz, od), has_aux=True)),
        "top": jax.jit(jax.value_and_grad(top, argnums=(0, 1), has_aux=True)),
        "back": jax.jit(lambda p, X, mask, ct: jax.vjp(lambda p_, X_: run_layer(p_, X_, mask), p, X)[1](ct)),
    }


def rollout_forward(params, tokens, is_first, actions, sz: Sizes, od=None, block: int = 4):
    """`forward` over all sequences [envs, T], `block` of them at a time: (the log-probabilities of `actions`
    [envs, T], values [envs, T], the expert choices [expert layers, envs, T, k])."""
    run = _programs(sz, od)["block"]
    parts = [run(params, tokens[i: i + block], is_first[i: i + block], actions[i: i + block]) for i in range(0, tokens.shape[0], block)]
    return (jnp.concatenate([x[0] for x in parts]), jnp.concatenate([x[1] for x in parts]), jnp.concatenate([x[2] for x in parts], 1))


def grads_by_layer(params, mb, coefs, sz: Sizes, od=None):
    """The gradient of `loss` a layer at a time: forward keeping each layer's input, then each layer's vjp from the
    head back. The same numbers as `jax.grad(loss)`, with one layer's intermediates alive at a time."""
    run = _programs(sz, od)
    mask = episode_mask(mb["is_first"])
    inputs = [run["embed"](params, mb["tokens"])]
    for i in range(sz.layers):
        inputs.append(run["layer"](params[f"layer_{i}"], inputs[-1], mask))
    rest = {k: v for k, v in params.items() if not k.startswith("layer_") and k != "embed"}
    (_, aux), (g_rest, dX) = run["top"](rest, inputs.pop(), mb, coefs)
    grads = dict(g_rest)
    for i in reversed(range(sz.layers)):
        grads[f"layer_{i}"], dX = run["back"](params[f"layer_{i}"], inputs.pop(), mask, dX)
    grads["embed"] = {"embedding": jnp.zeros_like(params["embed"]["embedding"]).at[mb["tokens"]].add(jnp.sum(dX, 2))}
    return aux, grads


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam_leaf(p, m, v, g, scale, lr, eps, c1, c2):
    g = g * scale
    m, v = 0.9 * m + 0.1 * g, 0.999 * v + 0.001 * jnp.square(g)
    return p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps), m, v


def adam_step(flat_params, flat_m, flat_v, flat_g, count: int, lr: float, sz: Sizes):
    """One Adam step leaf by leaf, in place (the three trees are donated), after clipping by the global norm."""
    scale = 1.0
    if sz.max_grad_norm > 0:
        norm = math.sqrt(sum(float(jnp.sum(jnp.square(g))) for g in flat_g.values()))
        scale = 1.0 if norm < sz.max_grad_norm else sz.max_grad_norm / norm
    c1, c2 = 1 - 0.9 ** count, 1 - 0.999 ** count
    for k in flat_params:
        flat_params[k], flat_m[k], flat_v[k] = _adam_leaf(flat_params[k], flat_m[k], flat_v[k], flat_g[k], scale, lr, sz.eps, c1, c2)


def first_update(weights: Dict[str, jax.Array], data: Dict[str, np.ndarray], coefs: Dict[str, float], key_data: np.ndarray,
                 sz: Sizes, od=None, whole: bool = False) -> Tuple[Dict[str, jax.Array], List[Dict[str, float]]]:
    """`epochs` x `minibatches` Adam steps on `data` (sequence-major [envs, T]: tokens, actions, is_first, logprobs,
    values, returns, advantages) from the seeded `weights`, WHICH ARE CONSUMED (the steps donate every leaf: beside
    Adam's moments and a gradient there is no room for a second copy): (the parameters after, flat; the losses of
    every step). `whole` takes each gradient by `jax.grad` of the whole loss instead of a layer at a time (small
    sizes)."""
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
            adam_step(flat, m, v, flatten(grads), count, sz.lr * float(coefs["lr_frac"]), sz)
            steps.append({k: float(x) for k, x in aux.items()})
    return flat, steps
