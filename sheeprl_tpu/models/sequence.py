"""Blocks of a sequence policy: latent attention in its two forms, a routed
expert layer that is told which experts it holds, manifold-constrained
hyper-connections, RMSNorm and YaRN rotary embeddings.

Plain functions over a nested dict of parameters (a leaf is `kernel`, `scale`,
`embedding` or a named vector), so that one tree serves the update's full
forward over `[B, T]` sequences and the player's one-token decode step through
a latent cache. `howto/sequence_policy.md` has the recipe and what each key of
`SequenceConfig` means; the equations are written out in the benchmark's plain
reference (`perfbench/references/ppo_recurrent_sequence.py`), which shares nothing with
this file but the names of the leaves.

One chip's share of a layer: `heads_held` heads and `vocab_held` ids are all
the tree holds of either (the tensor-parallel slices of the absent chips are
not here, nor is their all-reduce), and the expert layer routes over all
`n_routed_experts` but sums over the `experts_held` experts from
`first_expert` on: the pairs routed elsewhere are another chip's work.

The residual streams are kept `[n, *rows, C]`, streams leading: every mix of
streams is then an elementwise multiply-add over `[rows, C]` tiles with a
per-row coefficient, and the Sinkhorn rounds run over `[n, n, rows]`.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Tuple, get_type_hints

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]
NEG = -1e30  # a masked score


class SequenceConfig(NamedTuple):
    hidden_size: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    num_attention_heads: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    n_shared_experts: int
    routed_scaling_factor: float
    first_k_dense_replace: int
    num_hidden_layers: int
    hc_mult: int
    hc_sinkhorn_iters: int
    hc_eps: float
    mhc_h_res_clamp_min: float
    mhc_h_res_clamp_max: float
    rope_theta: float
    rope_factor: float
    rope_beta_fast: float
    rope_beta_slow: float
    rope_mscale_all_dim: float
    rope_original_max_position_embeddings: int
    vocab_size: int
    rms_norm_eps: float
    # the share of one chip
    experts_held: int
    first_expert: int
    heads_held: int
    vocab_held: int

    @classmethod
    def from_node(cls, node: Any) -> "SequenceConfig":
        """From the recipe's `algo.backbone` node: the published keys under their published names."""
        rope = node["rope_scaling"]
        kw = {f: node[f] for f in cls._fields if not f.startswith("rope_") or f == "rope_theta"}
        kw.update({f"rope_{k}": rope[k] for k in ("factor", "beta_fast", "beta_slow", "mscale_all_dim", "original_max_position_embeddings")})
        types = get_type_hints(cls)
        cfg = cls(**{f: types[f](kw[f]) for f in cls._fields})
        if not (0 <= cfg.first_expert and cfg.first_expert + cfg.experts_held <= cfg.n_routed_experts):
            raise ValueError(f"experts {cfg.first_expert}..{cfg.first_expert + cfg.experts_held} are not among {cfg.n_routed_experts}")
        if not (0 < cfg.heads_held <= cfg.num_attention_heads and 0 < cfg.vocab_held <= cfg.vocab_size):
            raise ValueError("heads_held and vocab_held are shares of num_attention_heads and vocab_size")
        return cfg

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def score_scale(self) -> float:
        m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0 if self.rope_factor > 1 else 1.0
        return self.qk_head_dim ** -0.5 * m * m

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    def expert_rows(self, tokens: int) -> int:
        """Rows of the grouped expert product for `tokens` tokens: every pair that CAN be routed here has a slot."""
        return tokens * min(self.num_experts_per_tok, self.experts_held)


# -- parameters ----------------------------------------------------------------------
def init_params(cfg: SequenceConfig, key: jax.Array) -> Params:
    keys = iter(jax.random.split(key, 64 * (cfg.num_hidden_layers + 1)))

    def kernel(*shape: int) -> Dict[str, jax.Array]:
        fan_in = shape[-2]
        return {"kernel": jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(fan_in)}

    def scale(n: int) -> Dict[str, jax.Array]:
        return {"scale": jnp.ones((n,), jnp.float32)}

    def gated(width: int, *lead: int) -> Params:
        C = cfg.hidden_size
        return {"w_gate": kernel(*lead, C, width), "w_up": kernel(*lead, C, width), "w_down": kernel(*lead, width, C)}

    def hyper() -> Params:
        n, C = cfg.hc_mult, cfg.hidden_size
        return {
            "phi_pre": kernel(n * C, n), "phi_post": kernel(n * C, n), "phi_res": kernel(n * C, n * n),
            "a_pre": jnp.full((), 0.01), "a_post": jnp.full((), 0.01), "a_res": jnp.full((), 0.01),
            "b_pre": jnp.zeros((n,)), "b_post": jnp.zeros((n,)), "b_res": jnp.zeros((n, n)),
            "norm": scale(C),
        }

    C, H = cfg.hidden_size, cfg.heads_held
    params: Params = {
        "embed": {"embedding": jax.random.normal(next(keys), (cfg.vocab_held, C), jnp.float32)},
        "final_norm": scale(C),
        "head": kernel(C, cfg.vocab_held),
        "value": {**kernel(C, 1), "bias": jnp.zeros((1,))},
    }
    for i in range(cfg.num_hidden_layers):
        layer: Params = {
            "attn_hc": hyper(),
            "attn": {
                "w_dq": kernel(C, cfg.q_lora_rank), "q_norm": scale(cfg.q_lora_rank),
                "w_uq": kernel(cfg.q_lora_rank, H * cfg.qk_head_dim),
                "w_dkv": kernel(C, cfg.kv_lora_rank + cfg.qk_rope_head_dim), "kv_norm": scale(cfg.kv_lora_rank),
                "w_ukv": kernel(cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                "w_o": kernel(H * cfg.v_head_dim, C),
            },
            "ffn_hc": hyper(),
        }
        if cfg.is_dense(i):
            layer["mlp"] = gated(cfg.intermediate_size)
        else:
            layer["moe"] = {
                "router": kernel(C, cfg.n_routed_experts), "e_bias": jnp.zeros((cfg.n_routed_experts,)),
                "experts": gated(cfg.moe_intermediate_size, cfg.experts_held),
                "shared": gated(cfg.moe_intermediate_size * cfg.n_shared_experts),
            }
        params[f"layer_{i}"] = layer
    return params


# -- norms and rotary ----------------------------------------------------------------
def rms_norm(x: jax.Array, scale: Any, eps: float) -> jax.Array:
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y if scale is None else y * scale


def yarn_inv_freq(cfg: SequenceConfig) -> np.ndarray:
    """Rotary frequencies, blended (YaRN) between the trained ones, where a dimension turns more than `beta_fast`
    times over the original context, and those divided by `factor`, where it turns fewer than `beta_slow` times."""
    d, base = cfg.qk_rope_head_dim, cfg.rope_theta
    trained = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if cfg.rope_factor <= 1:
        return trained.astype(np.float32)

    def turns_dim(turns: float) -> float:
        return d * math.log(cfg.rope_original_max_position_embeddings / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(turns_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(turns_dim(cfg.rope_beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (trained / cfg.rope_factor * ramp + trained * (1.0 - ramp)).astype(np.float32)


def rope(x: jax.Array, positions: jax.Array, inv_freq: np.ndarray) -> jax.Array:
    """Rotate the halves of the last axis; `positions` broadcasts against `x`'s leading axes."""
    angle = positions[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = jnp.split(x, 2, -1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# -- hyper-connections ---------------------------------------------------------------
def hc_coefficients(p: Params, X: jax.Array, cfg: SequenceConfig) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(H_pre [n, *rows], H_post [n, *rows], H_res [n, n, *rows]) from the streams `X` [n, *rows, C]."""
    n, C = cfg.hc_mult, cfg.hidden_size
    phi = jnp.concatenate([p["phi_pre"]["kernel"], p["phi_post"]["kernel"], p["phi_res"]["kernel"]], 1).reshape(n, C, n * (n + 2))
    inv_rms = jax.lax.rsqrt(jnp.mean(jnp.square(X), (0, -1)) + cfg.rms_norm_eps)  # of vec(X): over streams and channels
    proj = jnp.moveaxis(jnp.einsum("j...c,jck->...k", X, phi) * inv_rms[..., None], -1, 0)  # [n(n+2), *rows]
    ones = (1,) * (X.ndim - 2)
    pre = jax.nn.sigmoid(p["a_pre"] * proj[:n] + p["b_pre"].reshape(n, *ones))
    post = 2.0 * jax.nn.sigmoid(p["a_post"] * proj[n:2 * n] + p["b_post"].reshape(n, *ones))
    res = p["a_res"] * proj[2 * n:].reshape(n, n, *proj.shape[1:]) + p["b_res"].reshape(n, n, *ones)
    M = jnp.exp(jnp.clip(res, cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max))
    for _ in range(cfg.hc_sinkhorn_iters):
        M = M / (jnp.sum(M, 1, keepdims=True) + cfg.hc_eps)  # rows
        M = M / (jnp.sum(M, 0, keepdims=True) + cfg.hc_eps)  # columns
    return pre, post, M


def hc_read(p: Params, X: jax.Array, pre: jax.Array, cfg: SequenceConfig) -> jax.Array:
    """The sublayer's input: the streams mixed by H_pre, normalised with gain."""
    return rms_norm(jnp.sum(pre[..., None] * X, 0), p["norm"]["scale"], cfg.rms_norm_eps)


def hc_write(X: jax.Array, y: jax.Array, post: jax.Array, res: jax.Array) -> jax.Array:
    """X' = H_res X + H_post^T y."""
    return jnp.sum(res[..., None] * X[None], 1) + post[..., None] * y[None]


# -- latent attention ----------------------------------------------------------------
def _queries(p: Params, u: jax.Array, positions: jax.Array, cfg: SequenceConfig, inv_freq: np.ndarray):
    cq = rms_norm(u @ p["w_dq"]["kernel"], p["q_norm"]["scale"], cfg.rms_norm_eps)
    q = (cq @ p["w_uq"]["kernel"]).reshape(*u.shape[:-1], cfg.heads_held, cfg.qk_head_dim)
    q_nope, q_rope = q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
    return q_nope, rope(q_rope, positions[..., None], inv_freq)


def _latents(p: Params, u: jax.Array, positions: jax.Array, cfg: SequenceConfig, inv_freq: np.ndarray):
    ckv = u @ p["w_dkv"]["kernel"]
    c_kv = rms_norm(ckv[..., :cfg.kv_lora_rank], p["kv_norm"]["scale"], cfg.rms_norm_eps)
    return c_kv, rope(ckv[..., cfg.kv_lora_rank:], positions, inv_freq)


def _w_ukv(p: Params, cfg: SequenceConfig) -> jax.Array:
    return p["w_ukv"]["kernel"].reshape(cfg.kv_lora_rank, cfg.heads_held, cfg.qk_nope_head_dim + cfg.v_head_dim)


def mla_train(p: Params, u: jax.Array, positions: jax.Array, mask: jax.Array, cfg: SequenceConfig, inv_freq: np.ndarray) -> jax.Array:
    """The training form over `u` [B, T, C]: keys and values expanded per head; `mask` [B, T, T] is causal and same-episode."""
    q_nope, q_rope = _queries(p, u, positions, cfg, inv_freq)
    c_kv, k_rope = _latents(p, u, positions, cfg, inv_freq)
    kv = jnp.einsum("bsc,chd->bshd", c_kv, _w_ukv(p, cfg))
    k_nope, v = kv[..., :cfg.qk_nope_head_dim], kv[..., cfg.qk_nope_head_dim:]
    s = (jnp.einsum("bthd,bshd->bhts", q_nope, k_nope) + jnp.einsum("bthd,bsd->bhts", q_rope, k_rope)) * cfg.score_scale
    a = jax.nn.softmax(jnp.where(mask[:, None], s, NEG), -1)
    o = jnp.einsum("bhts,bshd->bthd", a, v)
    return o.reshape(*u.shape[:-1], -1) @ p["w_o"]["kernel"]


def mla_decode(p: Params, u: jax.Array, latents: jax.Array, layer: int, pos: jax.Array, start: jax.Array, cfg: SequenceConfig,
               inv_freq: np.ndarray, write: bool = True) -> Tuple[jax.Array, jax.Array]:
    """The acting form for one token an env, `u` [N, C]: the cache (`latents[layer]`, [N, S, `cache_width`]) holds
    `(c_kv, k_rope)` only, in its first `kv_lora_rank + rope` columns; `q_nope` is absorbed through W_uk and the
    weighted sum of `c_kv` goes through W_uv.
    Rows `start[e]..pos - 1` are read and the token attends to itself beside them; with `write` its own latent goes
    to row `pos`, in place. Returns (output [N, C], all layers' latents)."""
    r = cfg.kv_lora_rank
    positions = jnp.full(u.shape[:1], pos)
    q_nope, q_rope = _queries(p, u, positions, cfg, inv_freq)
    c_kv, k_rope = _latents(p, u, positions, cfg, inv_freq)
    w = _w_ukv(p, cfg)
    q_lat = jnp.einsum("nhd,chd->nhc", q_nope, w[..., :cfg.qk_nope_head_dim])
    cache = latents[layer]
    s = jnp.einsum("nhc,nsc->nhs", q_lat, cache[..., :r]) + jnp.einsum("nhd,nsd->nhs", q_rope, cache[..., r:r + cfg.qk_rope_head_dim])
    own = jnp.einsum("nhc,nc->nh", q_lat, c_kv) + jnp.einsum("nhd,nd->nh", q_rope, k_rope)
    at = jnp.arange(cache.shape[1])
    live = (at >= start[:, None]) & (at < pos)
    a = jax.nn.softmax(jnp.concatenate([jnp.where(live[:, None], s, NEG), own[..., None]], -1) * cfg.score_scale, -1)
    o_lat = jnp.einsum("nhs,nsc->nhc", a[..., :-1], cache[..., :r]) + a[..., -1:] * c_kv[:, None]
    o = jnp.einsum("nhc,chd->nhd", o_lat, w[..., cfg.qk_nope_head_dim:])
    if write:
        latents = jax.lax.dynamic_update_slice(latents, jnp.concatenate([c_kv, k_rope], -1)[None, :, None], (layer, 0, pos, 0))
    return o.reshape(u.shape[0], -1) @ p["w_o"]["kernel"], latents


# -- feed-forward ----------------------------------------------------------------------
def gated_mlp(p: Params, u: jax.Array) -> jax.Array:
    return (jax.nn.silu(u @ p["w_gate"]["kernel"]) * (u @ p["w_up"]["kernel"])) @ p["w_down"]["kernel"]


def route(p: Params, u: jax.Array, cfg: SequenceConfig) -> Tuple[jax.Array, jax.Array]:
    """(chosen experts [R, k] among all `n_routed_experts`, their weights [R, k]): sigmoid scores, the choice by
    score plus a bias that gets no gradient, the weights the chosen scores normalised and scaled."""
    s = jax.nn.sigmoid(u @ p["router"]["kernel"])
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(p["e_bias"]), cfg.num_experts_per_tok)
    sk = jnp.take_along_axis(s, idx, -1)
    return idx, sk / (jnp.sum(sk, -1, keepdims=True) + 1e-20) * cfg.routed_scaling_factor


def moe(p: Params, u: jax.Array, cfg: SequenceConfig, choices: bool = False, grouped: bool = True) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The expert layer over `u` [R, C]: dropless with static shapes, in two forms that agree. Returns the load beside
    the output: the pairs computed, the rows multiplied, the pairs left out (none) and the fullest held expert's load
    over the mean.

    `grouped` (the training form): the (token, expert) pairs routed to the experts held here are sorted by expert into
    a buffer with a slot for every pair that can come (`expert_rows`), multiplied as one grouped product per kernel,
    weighted and summed back by token.

    Not `grouped` (the acting form, one token an env): every held expert multiplies every row, and the routing's
    weights, zero where an expert was not chosen, sum the results. No sort and no gather, and the same work whatever
    the routing: the grouped product skips an expert no row chose, so a decode step's time followed the router's
    skew (4.02-4.25 ms a step over four seeds of the weights on a v5e, `PERF.md` Findings, PR 38), and this form
    takes the same time at every seed. It reads every held expert's kernels every step and multiplies
    `experts_held / top-k` times the rows; at a decode step's row count both stay under the time the kernels' bytes take."""
    R, k, E = u.shape[0], cfg.num_experts_per_tok, cfg.experts_held
    idx, w = route(p, u, cfg)
    local = idx - cfg.first_expert
    here = (local >= 0) & (local < E)
    e = p["experts"]
    if grouped:
        local = jnp.where(here, local, E).reshape(-1)  # pairs of the absent chips sort last
        M = cfg.expert_rows(R)
        order = jnp.argsort(local, stable=True)[:M]
        sizes = jnp.bincount(local, length=E + 1)[:E].astype(jnp.int32)
        routed = jnp.minimum(jnp.sum(sizes), M)  # what the buffer holds of what came: all of it, M is the worst case
        live = (jnp.arange(M) < routed)[:, None]
        token = order // k
        with jax.named_scope("experts"):
            x = jnp.where(live, u[token], 0.0)
            h = jax.nn.silu(jax.lax.ragged_dot(x, e["w_gate"]["kernel"], sizes)) * jax.lax.ragged_dot(x, e["w_up"]["kernel"], sizes)
            y = jnp.where(live, jax.lax.ragged_dot(h, e["w_down"]["kernel"], sizes), 0.0)
        y = y * w.reshape(-1)[order][:, None]
        routed_out = jnp.zeros_like(u).at[token].add(y)
    else:
        M = R * E
        chose = local[..., None] == jnp.arange(E)  # [R, k, E]
        weight = jnp.sum(jnp.where(chose, w[..., None], 0.0), 1)  # [R, E]
        sizes = jnp.sum(chose, (0, 1)).astype(jnp.int32)
        routed = jnp.sum(sizes)
        with jax.named_scope("experts"):  # one plain product per expert and kernel: a batched one came out a tenth slower on the chip
            routed_out = sum(weight[:, i:i + 1] * gated_mlp(jax.tree.map(lambda x: x[i], e), u) for i in range(E))
    out = routed_out + gated_mlp(p["shared"], u)
    load = {"routed_here": routed, "rows": jnp.asarray(M, jnp.int32), "dropped": jnp.sum(here) - routed,
            "max_over_mean": jnp.max(sizes) * E / jnp.maximum(routed, 1).astype(jnp.float32)}
    if choices:  # for a comparison of routings: the chosen experts of every token, sorted
        load["chosen"] = jnp.sort(idx, -1)
    return out, load


# -- the whole model -------------------------------------------------------------------
def _sublayer(hc: Params, X: jax.Array, cfg: SequenceConfig, scope: str, fn) -> Tuple[jax.Array, Any]:
    with jax.named_scope("mhc"):
        pre, post, res = hc_coefficients(hc, X, cfg)
        u = hc_read(hc, X, pre, cfg)
    with jax.named_scope(scope):
        y, aux = fn(u)
    with jax.named_scope("mhc"):
        return hc_write(X, y, post, res), aux


def _ffn(layer: Params, X: jax.Array, cfg: SequenceConfig, choices: bool = False, grouped: bool = True) -> Tuple[jax.Array, Any]:
    rows = X.shape[1:-1]
    if "mlp" in layer:
        return _sublayer(layer["ffn_hc"], X, cfg, "dense_mlp", lambda u: (gated_mlp(layer["mlp"], u), None))

    def experts(u):
        y, load = moe(layer["moe"], u.reshape(-1, u.shape[-1]), cfg, choices, grouped)
        return y.reshape(*rows, -1), load

    return _sublayer(layer["ffn_hc"], X, cfg, "moe", experts)


def _head(params: Params, X: jax.Array, cfg: SequenceConfig) -> Tuple[jax.Array, jax.Array]:
    with jax.named_scope("head"):
        x = rms_norm(jnp.sum(X, 0), params["final_norm"]["scale"], cfg.rms_norm_eps)
        return x @ params["head"]["kernel"], (x @ params["value"]["kernel"] + params["value"]["bias"])[..., 0]


def _embed(params: Params, tokens: jax.Array, cfg: SequenceConfig) -> jax.Array:
    with jax.named_scope("embed"):
        return jnp.broadcast_to(params["embed"]["embedding"][tokens], (cfg.hc_mult, *tokens.shape, cfg.hidden_size))


def _sum_loads(loads) -> Dict[str, jax.Array]:
    loads = [x for x in loads if x is not None]
    if not loads:
        zero = jnp.zeros((), jnp.int32)
        return {"routed_here": zero, "rows": zero, "dropped": zero, "max_over_mean": jnp.zeros(())}
    how = {"max_over_mean": jnp.max, "chosen": lambda x: x}  # the choices stay per layer: [expert layers, rows, k]
    return {k: how.get(k, jnp.sum)(jnp.stack([x[k] for x in loads])) for k in loads[0]}


def forward_train(params: Params, tokens: jax.Array, is_first: jax.Array, cfg: SequenceConfig, remat: bool = True,
                  choices: bool = False):
    """The full forward over `tokens` [B, T] (ids of the held slice) with `is_first` [B, T] marking episode starts:
    (logits [B, T, vocab_held], values [B, T], the expert layers' load; with `choices` also every token's chosen
    experts under `chosen`). One remat boundary a layer."""
    inv_freq = yarn_inv_freq(cfg)
    T = tokens.shape[1]
    positions = jnp.arange(T)
    episode = jnp.cumsum(is_first.astype(jnp.int32), 1)
    mask = (episode[:, :, None] == episode[:, None, :]) & (positions[:, None] >= positions[None, :])

    def layer_fn(layer: Params, X: jax.Array):
        X, _ = _sublayer(layer["attn_hc"], X, cfg, "mla", lambda u: (mla_train(layer["attn"], u, positions, mask, cfg, inv_freq), None))
        return _ffn(layer, X, cfg, choices)

    X = _embed(params, tokens, cfg)
    loads = []
    for i in range(cfg.num_hidden_layers):
        X, load = (jax.checkpoint(layer_fn) if remat else layer_fn)(params[f"layer_{i}"], X)
        loads.append(load)
    logits, values = _head(params, X, cfg)
    return logits, values, _sum_loads(loads)


def cache_width(cfg: SequenceConfig) -> int:
    """The cache's last axis: `kv_lora_rank + qk_rope_head_dim` rounded up to whole 128-wide lanes; the pad is never
    read. The decode step writes one position for every env and layer, so the cache must be held row-major: the TPU's
    default layout for `[.., capacity, 576]` puts the capacity axis minor-most (576 is no multiple of 128) and each
    step relaid the whole cache out and back, while for `[.., capacity, 640]` it is row-major, in the bytes the
    row-major tiles of 576 take anyway. Not a layout pinned at the jit boundary: an executable read back from the
    persistent compilation cache returns its output in the default layout (jax 0.9)."""
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    return -(-width // 128) * 128


def new_cache(cfg: SequenceConfig, num_envs: int, capacity: int) -> Dict[str, jax.Array]:
    """The per-env latent cache: per layer `[num_envs, capacity, cache_width]`, the write position, and per env the
    position its episode started at."""
    return {
        "latents": jnp.zeros((cfg.num_hidden_layers, num_envs, capacity, cache_width(cfg)), jnp.float32),
        "pos": jnp.zeros((), jnp.int32),
        "start": jnp.zeros((num_envs,), jnp.int32),
    }


def forward_decode(params: Params, cache: Dict[str, jax.Array], tokens: jax.Array, is_first: jax.Array, cfg: SequenceConfig,
                   write: bool = True):
    """One token an env through the cache: `tokens` [N], `is_first` [N] (an env's context restarts where it is set).
    Returns (logits [N, vocab_held], values [N], the cache one position on). Without `write` the cache is read only
    and comes back as it was: the value of a token that is not part of the rollout (a truncation's bootstrap)."""
    inv_freq = yarn_inv_freq(cfg)
    pos = cache["pos"]
    start = jnp.where(is_first, pos, cache["start"])
    latents = cache["latents"]
    X = _embed(params, tokens, cfg)
    for i in range(cfg.num_hidden_layers):
        layer = params[f"layer_{i}"]

        def attend(u, layer=layer, i=i, latents=latents):
            return mla_decode(layer["attn"], u, latents, i, pos, start, cfg, inv_freq, write)

        X, latents = _sublayer(layer["attn_hc"], X, cfg, "mla", attend)
        X, _ = _ffn(layer, X, cfg, grouped=False)
    logits, values = _head(params, X, cfg)
    return logits, values, {"latents": latents, "pos": pos + 1, "start": start} if write else cache
