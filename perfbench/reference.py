"""Plain float32 reference of one DreamerV3 gradient step (Hafner et al. 2023,
as the sheeprl recipes configure it): world-model loss over a [T, B] batch
(CNN + MLP encoder, RSSM scan with ``is_first`` resets, CNN + MLP decoder,
two-hot reward head, continue head, balanced KL with free nats), the
imagination rollout with the actor and critic losses, Adam with global-norm
clipping for the three groups, the return-percentile moments and the target
critic's moving average.

Straightforward ``jax.numpy``: no kernels, no donation, everything held and
summed in float32, every matmul and conv at ``Precision.HIGHEST``. It imports
nothing of the program. What it shares with the program is the *names* of the
weight leaves (so that the same seeded weights go to both) and the way the
step's PRNG key is split (so that both draw the same categorical samples from
equal logits).

``od`` (operand dtype) rounds every matmul and conv operand to that type
before the float32 product. None is the reference: pure float32. bfloat16 is
what a TPU's default precision does to the `32-true` the configurations state
(read once, for PERF.md). float8_e4m3fn, the precision below that, is the
control.

Departures from the program, none of which changes the mathematics: the
imagined trajectory is computed without a gradient tape (for discrete actions
every use of it in the actor loss is behind a stop-gradient); LayerNorm uses
the two-pass variance.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
LN_EPS = 1e-3


class Sizes(NamedTuple):
    """What the reference needs of a cell. `check.sizes_for` fills every field
    from the composed config the program runs with and from the mix's file."""
    stoch: int
    discrete: int
    recurrent: int
    horizon: int
    actions: int
    unimix: float
    gamma: float
    lmbda: float
    ent_coef: float
    kl_dynamic: float
    kl_representation: float
    kl_free_nats: float
    tau: float
    moments_decay: float
    moments_max: float
    moments_low: float
    moments_high: float
    wm_lr: float
    wm_eps: float
    wm_clip: float
    actor_lr: float
    actor_eps: float
    actor_clip: float
    critic_lr: float
    critic_eps: float
    critic_clip: float
    image_keys: Tuple[str, ...]
    vector_keys: Tuple[str, ...]
    vector_decoder_keys: Tuple[str, ...]


# -- layers ------------------------------------------------------------------
def _q(x, od):
    if od is None:
        return x
    m = float(jnp.finfo(od).max)
    return jnp.clip(x, -m, m).astype(od).astype(jnp.float32)


def dense(p, x, od):
    y = jnp.dot(_q(x, od), _q(p["kernel"], od), precision=HI)
    return y + p["bias"] if "bias" in p else y


def layer_norm(p, x):
    p = p["LayerNorm_0"]
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def silu(x):
    return x * jax.nn.sigmoid(x)


def mlp(p, x, od):
    i = 0
    while f"dense_{i}" in p:
        x = silu(layer_norm(p[f"LayerNorm_{i}"], dense(p[f"dense_{i}"], x, od)))
        i += 1
    return x


def conv_s2(kernel, x, od):
    """4x4 conv, stride 2, padding 1, NHWC x HWIO."""
    return jax.lax.conv_general_dilated(
        _q(x, od), _q(kernel, od), (2, 2), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


def deconv_s2(kernel, x, od):
    """Transposed 4x4 conv, stride 2, padding 1 (doubles H and W). The kernel
    is stored [kh, kw, out, in]: the forward conv's kernel whose transpose
    this is, so it is flipped in space and its channel axes are swapped."""
    k = jnp.flip(kernel, (0, 1)).transpose(0, 1, 3, 2)  # -> HWIO with I = in
    return jax.lax.conv_general_dilated(
        _q(x, od), _q(k, od), (1, 1), ((2, 2), (2, 2)), lhs_dilation=(2, 2),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


def symlog(x):
    return jnp.sign(x) * jnp.log1p(jnp.abs(x))


def symexp(x):
    return jnp.sign(x) * jnp.expm1(jnp.abs(x))


def unimix(logits, discrete, mix):
    shp = logits.shape
    lg = logits.reshape(shp[:-1] + (-1, discrete))
    probs = (1 - mix) * jax.nn.softmax(lg, -1) + mix / discrete
    return jnp.log(probs).reshape(shp)


def sample_st(logits, discrete, key):
    """One-hot sample per categorical of ``discrete`` classes, with the
    straight-through gradient to the probabilities."""
    shp = logits.shape
    lg = jax.nn.log_softmax(logits.reshape(shp[:-1] + (-1, discrete)), -1)
    idx = jax.random.categorical(key, lg, axis=-1, shape=lg.shape[:-1])
    hot = jax.nn.one_hot(idx, discrete, dtype=lg.dtype)
    probs = jnp.exp(lg)
    return (jax.lax.stop_gradient(hot) + probs - jax.lax.stop_gradient(probs)).reshape(shp)


def twohot_bins(n):
    return symexp(jnp.linspace(-20.0, 20.0, n)).astype(jnp.float32)


def twohot_mean(logits):
    return jnp.sum(jax.nn.softmax(logits, -1) * twohot_bins(logits.shape[-1]), -1, keepdims=True)


def twohot_log_prob(logits, x):
    """x [..., 1] against logits [..., bins]: weights on the two bins that
    bracket x, inversely to their distance."""
    nb = logits.shape[-1]
    bins = twohot_bins(nb)
    below = jnp.clip(jnp.sum((bins <= x).astype(jnp.int32), -1) - 1, 0, nb - 1)
    above = jnp.clip(nb - jnp.sum((bins > x).astype(jnp.int32), -1), 0, nb - 1)
    equal = below == above
    d_below = jnp.where(equal, 1.0, jnp.abs(bins[below] - x[..., 0]))
    d_above = jnp.where(equal, 1.0, jnp.abs(bins[above] - x[..., 0]))
    total = d_below + d_above
    target = (jax.nn.one_hot(below, nb) * (d_above / total)[..., None]
              + jax.nn.one_hot(above, nb) * (d_below / total)[..., None])
    return jnp.sum(target * jax.nn.log_softmax(logits, -1), -1)


# -- the world model -----------------------------------------------------------
def encode(wm, obs, sz: Sizes, od):
    feats = []
    if sz.image_keys:
        p = wm["encoder"]["DV3CNNEncoder_0"]
        x = jnp.concatenate([obs[k] for k in sz.image_keys], -1)
        lead = x.shape[:-3]
        x = x.reshape((-1,) + x.shape[-3:])
        i = 0
        while f"conv_{i}" in p:
            x = silu(layer_norm(p[f"LayerNorm_{i}"], conv_s2(p[f"conv_{i}"]["kernel"], x, od)))
            i += 1
        feats.append(x.reshape(lead + (-1,)))
    if sz.vector_keys:
        p = wm["encoder"]["DV3MLPEncoder_0"]["MLP_0"]
        x = jnp.concatenate([symlog(obs[k]) for k in sz.vector_keys], -1)
        feats.append(mlp(p, x, od))
    return jnp.concatenate(feats, -1)


def stoch_head(p, x, od):
    x = silu(layer_norm(p["LayerNorm_0"], dense(p["Dense_0"], x, od)))
    return dense(p["logits"], x, od)


def recurrent(p, za, h, od):
    feat = silu(layer_norm(p["LayerNorm_0"], dense(p["mlp"], za, od)))
    y = layer_norm(p["gru"]["LayerNorm_0"], dense(p["gru"]["fused"], jnp.concatenate([feat, h], -1), od))
    reset, cand, update = jnp.split(y, 3, -1)
    cand = jnp.tanh(jax.nn.sigmoid(reset) * cand)
    update = jax.nn.sigmoid(update - 1.0)
    return update * cand + (1.0 - update) * h


def transition_logits(rssm, h, sz, od):
    return unimix(stoch_head(rssm["transition"], h, od), sz.discrete, sz.unimix)


def initial_state(rssm, batch, sz, od):
    h0 = jnp.broadcast_to(jnp.tanh(rssm["initial_recurrent_state"]), (batch, sz.recurrent))
    lg = transition_logits(rssm, h0, sz, od).reshape(batch, sz.stoch, sz.discrete)
    z0 = jax.nn.one_hot(jnp.argmax(lg, -1), sz.discrete, dtype=jnp.float32)
    return h0, z0.reshape(batch, -1)


def decode_image(p, latent, od):
    x = dense(p["fc"], latent, od)
    c0 = p["deconv_0"]["kernel"].shape[-1]
    side = int(round((x.shape[-1] // c0) ** 0.5))
    x = x.reshape((-1, side, side, c0))
    i = 0
    while f"deconv_{i}" in p:
        x = silu(layer_norm(p[f"LayerNorm_{i}"], deconv_s2(p[f"deconv_{i}"]["kernel"], x, od)))
        i += 1
    x = deconv_s2(p["to_obs"]["kernel"], x, od) + p["to_obs"]["bias"]
    return x.reshape(latent.shape[:-1] + x.shape[1:])


def head(p, x, od):
    return dense(p["out"], mlp(p["MLP_0"], x, od), od)


def world_model_loss(wm, batch, key, sz: Sizes, od):
    T, B = batch["rewards"].shape[:2]
    obs = {k: batch[k].astype(jnp.float32) / 255.0 - 0.5 for k in sz.image_keys}
    obs.update({k: batch[k] for k in sz.vector_keys})
    is_first = batch["is_first"].at[0].set(1.0)
    actions = jnp.concatenate([jnp.zeros_like(batch["actions"][:1]), batch["actions"][:-1]], 0)
    embedded = encode(wm, obs, sz, od)
    rssm = wm["rssm"]

    def step(carry, xs):
        h, z = carry
        a, e, first, k = xs
        a = (1 - first) * a
        h0, z0 = initial_state(rssm, B, sz, od)
        h = (1 - first) * h + first * h0
        z = (1 - first) * z + first * z0
        h = recurrent(rssm["recurrent_model"], jnp.concatenate([z, a], -1), h, od)
        prior = transition_logits(rssm, h, sz, od)
        post = unimix(stoch_head(rssm["representation"], jnp.concatenate([h, e], -1), od), sz.discrete, sz.unimix)
        z = sample_st(post, sz.discrete, k)
        return (h, z), (h, z, post, prior)

    keys = jax.random.split(key, T)
    init = (jnp.zeros((B, sz.recurrent)), jnp.zeros((B, sz.stoch * sz.discrete)))
    _, (hs, zs, post, prior) = jax.lax.scan(step, init, (actions, embedded, is_first, keys))
    latents = jnp.concatenate([zs, hs], -1)

    obs_loss = 0.0
    if sz.image_keys:
        recon = decode_image(wm["observation_model"]["DV3CNNDecoder_0"], latents, od)
        start = 0
        for k in sz.image_keys:
            c = obs[k].shape[-1]
            obs_loss = obs_loss + jnp.sum(jnp.square(recon[..., start:start + c] - obs[k]), (-3, -2, -1))
            start += c
    if sz.vector_decoder_keys:
        p = wm["observation_model"]["DV3MLPDecoder_0"]
        x = mlp(p["MLP_0"], latents, od)
        for k in sz.vector_decoder_keys:
            obs_loss = obs_loss + jnp.sum(jnp.square(dense(p[f"head_{k}"], x, od) - symlog(obs[k])), -1)
    reward_loss = -twohot_log_prob(head(wm["reward"], latents, od), batch["rewards"])
    cont_logits = head(wm["continue"], latents, od)
    cont_target = 1 - batch["terminated"]
    bce = jnp.maximum(cont_logits, 0) - cont_logits * cont_target + jnp.log1p(jnp.exp(-jnp.abs(cont_logits)))
    continue_loss = jnp.sum(bce, -1)

    lp_post = jax.nn.log_softmax(post.reshape(T, B, sz.stoch, sz.discrete), -1)
    lp_prior = jax.nn.log_softmax(prior.reshape(T, B, sz.stoch, sz.discrete), -1)
    sg = jax.lax.stop_gradient

    def kl(lp, lq):
        return jnp.sum(jnp.exp(lp) * (lp - lq), (-2, -1))

    dyn = sz.kl_dynamic * jnp.maximum(kl(sg(lp_post), lp_prior), sz.kl_free_nats)
    rep = sz.kl_representation * jnp.maximum(kl(lp_post, sg(lp_prior)), sz.kl_free_nats)
    loss = jnp.mean(dyn + rep + obs_loss + reward_loss + continue_loss)
    return loss, (zs, hs)


# -- behaviour -----------------------------------------------------------------
def actor_logits(actor, state, od):
    return dense(actor["head_0"], mlp(actor["MLP_0"], state, od), od)


def imagine(wm, actor, z, h, key, sz: Sizes, od):
    """[H+1, N, L] states and [H+1, N, A] actions, from N start states."""
    rssm = wm["rssm"]

    def act(state, k):
        lg = unimix(actor_logits(actor, state, od), sz.actions, sz.unimix)
        return sample_st(lg, sz.actions, jax.random.split(k, 1)[0])

    state0 = jnp.concatenate([z, h], -1)
    k0, key = jax.random.split(key)
    a0 = act(state0, k0)

    def step(carry, k):
        z, h, a = carry
        k_z, k_a = jax.random.split(k)
        h = recurrent(rssm["recurrent_model"], jnp.concatenate([z, a], -1), h, od)
        z = sample_st(transition_logits(rssm, h, sz, od), sz.discrete, k_z)
        state = jnp.concatenate([z, h], -1)
        a = act(state, k_a)
        return (z, h, a), (state, a)

    _, (states, actions) = jax.lax.scan(step, (z, h, a0), jax.random.split(key, sz.horizon))
    return (jnp.concatenate([state0[None], states], 0),
            jnp.concatenate([a0[None], actions], 0))


def lambda_values(rewards, values, continues, lmbda):
    interm = rewards + continues * values * (1 - lmbda)

    def step(carry, xs):
        r, c = xs
        lv = r + c * lmbda * carry
        return lv, lv

    _, lvs = jax.lax.scan(step, values[-1], (interm, continues), reverse=True)
    return lvs


# -- the optimizer ---------------------------------------------------------------
def adam_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": jax.tree.map(jnp.zeros_like, params), "count": jnp.zeros((), jnp.int32)}


def clip_global(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    return jax.tree.map(lambda g: jnp.where(norm < max_norm, g, g / norm * max_norm), grads)


def adam_update(params, grads, state, lr, eps, b1=0.9, b2=0.999):
    count = state["count"] + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g), state["nu"], grads)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    new = jax.tree.map(lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps), params, mu, nu)
    return new, {"mu": mu, "nu": nu, "count": count}


# -- one gradient step -------------------------------------------------------------
def init_state(params):
    return {
        "params": params,
        "opt": {g: adam_init(params[g]) for g in ("wm", "actor", "critic")},
        "moments": (jnp.zeros(()), jnp.zeros(())),
    }


def step(state, batch, key, sz: Sizes, od=None, faults: Tuple[str, ...] = ()):
    """One gradient step on a [T, B] batch. Returns (new state, losses,
    clipped gradients as the optimizer gets them). ``faults`` plants what the
    fault tests read: "half_batch" leaves out half of the batch's columns."""
    if "half_batch" in faults:
        batch = {k: v[:, : v.shape[1] // 2] for k, v in batch.items()}
    params, opt = dict(state["params"]), dict(state["opt"])
    T, B = batch["rewards"].shape[:2]
    k_dyn, k_img, _ = jax.random.split(key, 3)
    sg = jax.lax.stop_gradient

    (wm_loss, (zs, hs)), g_wm = jax.value_and_grad(world_model_loss, has_aux=True)(params["wm"], batch, k_dyn, sz, od)
    g_wm = clip_global(g_wm, sz.wm_clip)
    params["wm"], opt["wm"] = adam_update(params["wm"], g_wm, opt["wm"], sz.wm_lr, sz.wm_eps)

    wm = params["wm"]  # the imagination runs on the updated world model
    z0 = sg(zs).reshape(T * B, -1)
    h0 = sg(hs).reshape(T * B, -1)
    traj, acts = imagine(wm, params["actor"], z0, h0, k_img, sz, od)
    traj, acts = sg(traj), sg(acts)
    values = twohot_mean(head(params["critic"], traj, od))
    rewards = twohot_mean(head(wm["reward"], traj, od))
    continues = (jax.nn.sigmoid(head(wm["continue"], traj, od)) > 0.5).astype(jnp.float32)
    continues = jnp.concatenate([(1 - batch["terminated"]).reshape(1, T * B, 1), continues[1:]], 0)
    lv = lambda_values(rewards[1:], values[1:], continues[1:] * sz.gamma, sz.lmbda)
    discount = jnp.cumprod(continues * sz.gamma, 0) / sz.gamma
    low, high = state["moments"]
    low = sz.moments_decay * low + (1 - sz.moments_decay) * jnp.quantile(lv, sz.moments_low)
    high = sz.moments_decay * high + (1 - sz.moments_decay) * jnp.quantile(lv, sz.moments_high)
    invscale = jnp.maximum(1.0 / sz.moments_max, high - low)
    advantage = (lv - low) / invscale - (values[:-1] - low) / invscale

    def actor_loss(actor):
        lg = jax.nn.log_softmax(unimix(actor_logits(actor, traj, od), sz.actions, sz.unimix), -1)
        logprob = jnp.sum(acts * lg, -1, keepdims=True)[:-1]
        entropy = sz.ent_coef * -jnp.sum(jnp.exp(lg) * lg, -1, keepdims=True)
        return -jnp.mean(discount[:-1] * (logprob * advantage + entropy[:-1]))

    policy_loss, g_actor = jax.value_and_grad(actor_loss)(params["actor"])
    g_actor = clip_global(g_actor, sz.actor_clip)
    params["actor"], opt["actor"] = adam_update(params["actor"], g_actor, opt["actor"], sz.actor_lr, sz.actor_eps)

    target_values = twohot_mean(head(params["target_critic"], traj[:-1], od))

    def critic_loss(critic):
        lg = head(critic, traj[:-1], od)
        return jnp.mean((-twohot_log_prob(lg, lv) - twohot_log_prob(lg, target_values)) * discount[:-1, ..., 0])

    value_loss, g_critic = jax.value_and_grad(critic_loss)(params["critic"])
    g_critic = clip_global(g_critic, sz.critic_clip)
    params["critic"], opt["critic"] = adam_update(params["critic"], g_critic, opt["critic"], sz.critic_lr, sz.critic_eps)
    params["target_critic"] = jax.tree.map(
        lambda t, s: (1 - sz.tau) * t + sz.tau * s, params["target_critic"], params["critic"])

    new = {"params": params, "opt": opt, "moments": (low, high)}
    losses = {"wm": wm_loss, "actor": policy_loss, "critic": value_loss}
    return new, losses, {"wm": g_wm, "actor": g_actor, "critic": g_critic}


# -- the seeded weights, made by the benchmark for the program and the reference alike --
def _leaf(key, path: str, shape, dtype):
    name = path.rsplit("/", 1)[-1]
    if name == "kernel":
        fan_in = max(int(np.prod(shape[:-1])), 1) if len(shape) == 2 else max(int(np.prod(shape)) // max(shape[-2:]), 1)
        return (jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)).astype(dtype)
    if name == "scale":
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make_weights(seed: int, shapes: Dict[str, Tuple[Tuple[int, ...], Any]], device=None) -> Dict[str, jax.Array]:
    """Every leaf from the seed in one jitted call, on the device, in the type
    it is trained in. ``shapes`` maps 'wm/encoder/.../kernel' -> (shape, dtype).
    target_critic/* copies critic/*, as a fresh agent starts."""
    names = sorted(n for n in shapes if not n.startswith("target_critic/"))

    def build(key):
        out = {n: _leaf(jax.random.fold_in(key, i), n, *shapes[n]) for i, n in enumerate(names)}
        for n in shapes:
            if n.startswith("target_critic/"):
                out[n] = out["critic/" + n.split("/", 1)[1]] + 0.0
        return out

    if device is None:
        return jax.jit(build)(seed_key(seed))
    from jax.sharding import SingleDeviceSharding

    return jax.jit(build, out_shardings=SingleDeviceSharding(device))(seed_key(seed))


def nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for path, v in flat.items():
        d = out
        parts = path.split("/")
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = v
    return out


def flatten(tree: Any, prefix: str = "") -> Dict[str, Any]:
    if isinstance(tree, dict):
        out: Dict[str, Any] = {}
        for k in tree:
            out.update(flatten(tree[k], f"{prefix}/{k}" if prefix else str(k)))
        return out
    return {prefix: tree}
