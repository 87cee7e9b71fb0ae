"""A language model that generates by diffusion over blocks, as a sequence
policy: grouped-query attention with q/k RMSNorm and plain rotary embeddings,
a plain pre-norm residual, and the routed expert layer of `models/sequence.py`
(softmax routing, no shared expert), told which experts it holds.

A block of `L` tokens starts masked (every id the slice's `mask_id`); `S`
forward passes fill it in, attending bidirectionally within the block and
causally to the committed blocks before it, and each commits `L / S` of its
positions. As a policy on the recurrent on-policy loop an env step hands the
model one observed block `o_t` and takes one action block `a_t` back:

* positions: `o_t` lies at `L t .. L t + L - 1` of the rollout's context and
  `a_t` is denoised at `L (t + 1) ..`, where it will sit next;
* a token of a clean block `o_t` attends to every clean token of `o_1 .. o_t`
  of its episode (its own block whole); a token of the denoising copy
  `(t, s)` of `a_t` attends to the same clean tokens and to the `L` tokens of
  that copy, and to nothing else.

Two forms over one tree of parameters:

* `forward_train`, the update's: the clean sequence (`T L` rows) followed by
  `S` copies of every action block (`S T L` rows; copy `s` holds the ids
  committed before step `s`, `order < s`, and the mask id elsewhere). The
  attention is written in two parts under ONE softmax: every row against the
  clean keys under the block-causal same-episode mask, and each copy's own
  `L x L` block. The whole `(1 + S) T L` square is never formed. On a TPU the
  same attention runs as one blockwise kernel (`train_attention_kernel`) over
  the whole row set, whose tiles skip what the mask leaves out;
* `forward_decode`, the player's: a few rows an env through a per-env K/V
  cache (keys and values per KV head, `[layers, envs, KV, capacity, D]`),
  each row attending to its env's cached rows of the episode and, under a
  mask, to the rows of the call; the rows of a clean block are written.

The equations are written out in the benchmark's plain reference
(`perfbench/references/`), which shares nothing with this file but the names
of the leaves. `howto/block_diffusion_policy.md` has the recipe.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import sequence as seq
from .sequence import NEG, Params, SequenceConfig, rms_norm, rope, yarn_inv_freq


def kv_heads(cfg: SequenceConfig) -> int:
    """The KV heads of the held query heads: groups of `num_attention_heads / num_key_value_heads` share one."""
    return cfg.num_key_value_heads * cfg.heads_held // cfg.num_attention_heads


def init_params(cfg: SequenceConfig, key: jax.Array) -> Params:
    keys = iter(jax.random.split(key, 16 * (cfg.num_hidden_layers + 1)))

    def kernel(*shape: int) -> Dict[str, jax.Array]:
        return {"kernel": jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(shape[-2])}

    def scale(n: int) -> Dict[str, jax.Array]:
        return {"scale": jnp.ones((n,), jnp.float32)}

    def gated(width: int, *lead: int) -> Params:
        C = cfg.hidden_size
        return {"w_gate": kernel(*lead, C, width), "w_up": kernel(*lead, C, width), "w_down": kernel(*lead, width, C)}

    C, H, KV, D = cfg.hidden_size, cfg.heads_held, kv_heads(cfg), cfg.head_dim
    params: Params = {
        "embed": {"embedding": jax.random.normal(next(keys), (cfg.vocab_held, C), jnp.float32)},
        "final_norm": scale(C),
        "head": kernel(C, cfg.vocab_held),
        "value": {**kernel(C, 1), "bias": jnp.zeros((1,))},
    }
    for i in range(cfg.num_hidden_layers):
        params[f"layer_{i}"] = {
            "attn_norm": scale(C),
            "attn": {"w_q": kernel(C, H * D), "w_k": kernel(C, KV * D), "w_v": kernel(C, KV * D), "q_norm": scale(D),
                     "k_norm": scale(D), "w_o": kernel(H * D, C)},
            "ffn_norm": scale(C),
            "moe": seq.expert_layer(cfg, kernel, gated),
        }
    return params


def copy_mask(L: int) -> jax.Array:
    """What a token of a denoising copy sees of its own copy: all of it (bidirectional within the block)."""
    return jnp.ones((L, L), bool)


def pick_copies(copies: jax.Array, order: jax.Array) -> jax.Array:
    """Of every action position, the state of the copy that commits it: `copies` [B, T, S, L, C], `order` [B, T, L]
    (the step at which each position is committed) -> [B, T, L, C]."""
    return jnp.take_along_axis(copies, order[:, :, None, :, None], 2)[:, :, 0]


def project(p: Params, u: jax.Array, positions: jax.Array, cfg: SequenceConfig, inv_freq: np.ndarray):
    """(q [.., H, D], k [.., KV, D], v [.., KV, D]) of the rows `u` [.., C]: per-head RMSNorm of q and k, then both
    rotated at `positions` (broadcast against `u`'s leading axes)."""
    lead, D = u.shape[:-1], cfg.head_dim
    q = rms_norm((u @ p["w_q"]["kernel"]).reshape(*lead, -1, D), p["q_norm"]["scale"], cfg.rms_norm_eps)
    k = rms_norm((u @ p["w_k"]["kernel"]).reshape(*lead, -1, D), p["k_norm"]["scale"], cfg.rms_norm_eps)
    v = (u @ p["w_v"]["kernel"]).reshape(*lead, -1, D)
    at = positions[..., None]
    return rope(q, at, inv_freq), rope(k, at, inv_freq), v


def _scores(q: jax.Array, k: jax.Array, cfg: SequenceConfig) -> jax.Array:
    """q [B, *R, H, D] against k [B, S, KV, D] -> [B, KV, G, *R, S], the query heads grouped by the KV head they share."""
    B, *R, H, D = q.shape
    KV = k.shape[-2]
    qg = q.reshape(B, -1, KV, H // KV, D)
    s = jnp.einsum("brkgd,bskd->bkgrs", qg, k) * cfg.qk_head_dim ** -0.5
    return s.reshape(B, KV, H // KV, *R, k.shape[1])


def _weighted(a: jax.Array, v: jax.Array) -> jax.Array:
    """a [B, KV, G, *R, S] over v [B, S, KV, D] -> [B, *R, H, D]."""
    B, KV, G, *R, S = a.shape
    o = jnp.einsum("bkgrs,bskd->brkgd", a.reshape(B, KV, G, -1, S), v)
    return o.reshape(B, *R, KV * G, v.shape[-1])


def attend(q: jax.Array, parts, cfg: SequenceConfig) -> jax.Array:
    """One softmax over the keys of every part: q [B, R, H, D]; a part is (k [B, S_p, KV, D], v [B, S_p, KV, D],
    mask [B or 1, R, S_p]). Returns [B, R, H x D]."""
    scores = [jnp.where(m[:, None, None], _scores(q, k, cfg), NEG) for k, _, m in parts]
    a = jax.nn.softmax(jnp.concatenate(scores, -1), -1)
    cut = np.cumsum([k.shape[1] for k, _, _ in parts])[:-1]
    o = sum(_weighted(ai, v) for ai, (_, v, _) in zip(jnp.split(a, cut, -1), parts))
    return o.reshape(*q.shape[:2], -1)


def train_attention(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array, clean: int, L: int, cfg: SequenceConfig) -> jax.Array:
    """The update's attention in two parts. Rows [B, Q, ..]: the first `clean` are the clean sequence, the rest the
    copies, `L` rows each. Part 1: every row against the clean keys under `mask` [B, Q, clean]; part 2: each copy's
    rows against the copy's own keys under `copy_mask`. One softmax over both parts. Returns [B, Q, H x D]."""
    B, Q, H, D = q.shape
    n = (Q - clean) // L  # copies
    kc, vc = k[:, :clean], v[:, :clean]
    s1 = jnp.where(mask[:, None, None], _scores(q, kc, cfg), NEG)  # [B, KV, G, Q, clean]
    s2 = _scores(q[:, clean:].reshape(B * n, L, H, D), k[:, clean:].reshape(B * n, L, -1, D), cfg)  # [B n, KV, G, L, L]
    KV, G = s2.shape[1:3]
    s2 = jnp.where(copy_mask(L), jnp.moveaxis(s2.reshape(B, n, KV, G, L, L), 1, 3), NEG)  # [B, KV, G, n, L, L]
    a_clean = jax.nn.softmax(s1[..., :clean, :], -1)
    a = jax.nn.softmax(jnp.concatenate([s1[..., clean:, :].reshape(B, KV, G, n, L, clean), s2], -1), -1)
    o_clean = _weighted(a_clean, vc)  # [B, clean, H, D]
    vn = v[:, clean:].reshape(B * n, L, KV, D)
    own = jnp.moveaxis(a[..., clean:], 3, 1).reshape(B * n, KV, G, L, L)
    o_copy = _weighted(a[..., :clean].reshape(B, KV, G, n * L, clean), vc) + _weighted(own, vn).reshape(B, n * L, H, D)
    return jnp.concatenate([o_clean, o_copy], 1).reshape(B, Q, H * D)


def resolve_train_attention(form: str = "auto") -> str:
    """Which form of the update's attention runs: `"kernel"` (`train_attention_kernel`) or `"xla"` (`train_attention`).
    `"auto"` takes the kernel where the default backend is a TPU and the XLA form everywhere else; it asks
    `jax.default_backend()` when called, so resolve it once, where the update is built."""
    if form == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "xla"
    if form not in ("kernel", "xla"):
        raise ValueError(f"the update's attention is 'auto', 'kernel' or 'xla', got {form!r}")
    return form


# The kernel's tiles, rows x keys, forward and backward, and the backward fused into one kernel (dq beside dk and dv).
# At the update's 1536 rows (512 clean) on one TPU v5e, one layer's attention, forward, remat's recompute and backward,
# took 19.6 ms in tiles of 128, 10.2 in tiles of 256, 8.2 in tiles of 512 and 7.4 with the fused backward, against
# 20.8 for the XLA form: larger tiles compute more masked pairs (56 % of the tile pairs at 512, 26 % at 128) and
# still win, since a tile's fixed cost dominates at these sizes.
KERNEL_BLOCK_Q, KERNEL_BLOCK_KV = 512, 512
LANES = 128  # a tile's keys fill whole lanes: the row set is padded to a multiple


def kernel_rows(T: int, L: int, S: int) -> int:
    """The update's `(1 + S) T L` rows, padded to whole lanes."""
    return -(-(1 + S) * T * L // LANES) * LANES


def kernel_mask(T: int, L: int, S: int) -> np.ndarray:
    """The kernel's static mask over the padded row set, [n, n]: a row sees a clean key of its own or an earlier
    block, and the keys of its own copy (a clean row has none); a pad row sees itself alone, and no row sees a pad
    key. The episode is left to the segment ids."""
    n, clean, Q = kernel_rows(T, L, S), T * L, (1 + S) * T * L
    r = np.arange(n)
    block = np.where(r < clean, r // L, (r - clean) // (S * L))
    copy = np.where((r >= clean) & (r < Q), (r - clean) // L, -1)
    real = r < Q
    sees = (r[None, :] < clean) & (block[None, :] <= block[:, None]) | (copy[:, None] == copy[None, :]) & (copy[:, None] >= 0)
    return sees & real[:, None] & real[None, :] | np.diag(~real)


def _tile(n: int, want: int) -> int:
    """The largest multiple of the lane width that divides `n` and is at most `want`."""
    return max(b for b in range(LANES, min(n, want) + 1, LANES) if n % b == 0)


def kernel_blocks(T: int, L: int, S: int) -> Tuple[int, int]:
    n = kernel_rows(T, L, S)
    return _tile(n, KERNEL_BLOCK_Q), _tile(n, KERNEL_BLOCK_KV)


def attn_blocks_computed(T: int, L: int, S: int) -> float:
    """The share of the kernel's (row tile, key tile) pairs that the static mask leaves non-empty, for one sequence:
    the others are skipped."""
    (bq, bkv), n = kernel_blocks(T, L, S), kernel_rows(T, L, S)
    return float(kernel_mask(T, L, S).reshape(n // bq, bq, n // bkv, bkv).any((1, 3)).mean())


def train_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, segments: jax.Array, T: int, L: int, S: int, cfg: SequenceConfig,
                           interpret: bool = False) -> jax.Array:
    """`train_attention` as one blockwise kernel (jax's splash attention), so that no score or probability leaves the
    chip's fast memory: rows [B, Q, ..] as there, `segments` [B, Q] the episode of each row's block. `kernel_mask`
    and equal segments together are `train_attention`'s mask and copy mask under one softmax. One MQA call for each
    sequence and KV head, over the G query heads that share it; the backward is the kernel's own. float32 in and out:
    nothing is cast. `interpret` runs the kernel through the Pallas interpreter (the CPU's tests). Returns [B, Q, H x D]."""
    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    B, Q, H, D = q.shape
    KV = k.shape[2]
    G, n = H // KV, kernel_rows(T, L, S)
    bq, bkv = kernel_blocks(T, L, S)
    blocks = splash.BlockSizes(block_q=bq, block_kv=bkv, block_q_dkv=bq, block_kv_dkv=bkv, use_fused_bwd_kernel=True)
    kernel = splash.make_splash_mqa_single_device(splash.MultiHeadMask([splash.NumpyMask(kernel_mask(T, L, S))] * G),
                                                  block_sizes=blocks, interpret=interpret)
    pad = ((0, 0), (0, n - Q), (0, 0), (0, 0))
    qh = jnp.pad(q * cfg.qk_head_dim ** -0.5, pad).reshape(B, n, KV, G, D).transpose(0, 2, 3, 1, 4)  # [B, KV, G, n, D]
    kh, vh = (jnp.pad(x, pad).transpose(0, 2, 1, 3) for x in (k, v))  # [B, KV, n, D]
    seg = jnp.pad(segments, ((0, 0), (0, n - Q)), constant_values=-1)
    one = lambda q1, k1, v1, s1: kernel(q1, k1, v1, segment_ids=splash.SegmentIds(s1, s1))
    o = jax.vmap(jax.vmap(one, (0, 0, 0, None)), (0, 0, 0, 0))(qh, kh, vh, seg)  # [B, KV, G, n, D]
    return o.transpose(0, 3, 1, 2, 4)[:, :Q].reshape(B, Q, H * D)


def _head(params: Params, x: jax.Array, cfg: SequenceConfig, mask_id: int) -> jax.Array:
    """Logits over the held slice of the normed state; the mask id is never an answer."""
    logits = rms_norm(x, params["final_norm"]["scale"], cfg.rms_norm_eps) @ params["head"]["kernel"]
    return jnp.where(jnp.arange(cfg.vocab_held) == mask_id, NEG, logits)


def _value(params: Params, x: jax.Array, cfg: SequenceConfig) -> jax.Array:
    x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_norm_eps)
    return (x @ params["value"]["kernel"] + params["value"]["bias"])[..., 0]


def _ffn(layer: Params, X: jax.Array, cfg: SequenceConfig, fit: bool = False) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    with jax.named_scope("moe"):
        u = rms_norm(X, layer["ffn_norm"]["scale"], cfg.rms_norm_eps)
        y, load = seq.moe(layer["moe"], u.reshape(-1, u.shape[-1]), cfg, fit=fit)
        return X + y.reshape(X.shape), load


def forward_train(params: Params, tokens: jax.Array, is_first: jax.Array, actions: jax.Array, order: jax.Array, cfg: SequenceConfig,
                  denoising_steps: int, mask_id: int, remat: bool = True, attention: str = "xla"):
    """The update's forward: `tokens`, `actions`, `order` [B, T, L] (the observed blocks, the action blocks and the
    step at which each action position was committed), `is_first` [B, T]. Returns (logits [B, T, L, vocab_held] of
    every action position from the copy that commits it, values [B, T] at every clean block's last position, the
    expert layers' load). One remat boundary a layer. `attention` is the attention's form, `"xla"` or `"kernel"`
    (`resolve_train_attention`)."""
    B, T, L = tokens.shape
    S = denoising_steps
    inv_freq = yarn_inv_freq(cfg)
    steps = jnp.arange(S)[:, None]
    copies = jnp.where(order[:, :, None, :] < steps, actions[:, :, None, :], mask_id)  # [B, T, S, L]
    ids = jnp.concatenate([tokens.reshape(B, T * L), copies.reshape(B, T * S * L)], 1)
    block = jnp.arange(T)
    positions = jnp.concatenate([(L * block[:, None] + jnp.arange(L)).reshape(-1),
                                 jnp.broadcast_to((L * (block + 1))[:, None, None] + jnp.arange(L), (T, S, L)).reshape(-1)])
    sees = jnp.concatenate([jnp.repeat(block, L), jnp.repeat(block, S * L)])  # the last clean block each row may see
    seen = jnp.repeat(block, L)
    episode = jnp.cumsum(is_first.astype(jnp.int32), 1)
    if resolve_train_attention(attention) == "kernel":
        attend_rows = partial(train_attention_kernel, segments=episode[:, sees], T=T, L=L, S=S, cfg=cfg)
    else:
        mask = (seen[None, None, :] <= sees[None, :, None]) & (episode[:, sees][:, :, None] == episode[:, seen][:, None, :])
        attend_rows = partial(train_attention, mask=mask, clean=T * L, L=L, cfg=cfg)

    def layer_fn(layer: Params, X: jax.Array):
        with jax.named_scope("bd_attn"):
            u = rms_norm(X, layer["attn_norm"]["scale"], cfg.rms_norm_eps)
            q, k, v = project(layer["attn"], u, positions, cfg, inv_freq)
            X = X + attend_rows(q, k, v) @ layer["attn"]["w_o"]["kernel"]
        return _ffn(layer, X, cfg, fit=True)

    with jax.named_scope("embed"):
        X = params["embed"]["embedding"][ids]
    loads = []
    for i in range(cfg.num_hidden_layers):
        X, load = (jax.checkpoint(layer_fn) if remat else layer_fn)(params[f"layer_{i}"], X)
        loads.append(load)
    with jax.named_scope("head"):
        values = _value(params, X[:, L - 1:T * L:L], cfg)
        logits = _head(params, pick_copies(X[:, T * L:].reshape(B, T, S, L, -1), order), cfg, mask_id)
    return logits, values, seq._sum_loads(loads)


def new_cache(cfg: SequenceConfig, num_envs: int, capacity: int) -> Dict[str, jax.Array]:
    """The per-env K/V cache: per layer `[num_envs, KV, capacity, head_dim]` keys and values, the next write position,
    and per env the position its episode started at. KV heads before positions, as the attention's products take
    them: held `[.., capacity, KV x head_dim]`, the TPU's compiler relaid both whole caches out and back around every
    decode step (four cache-sized copies in the program compiled for a v5e; `tests/test_chip_compile.py` holds it)."""
    shape = (cfg.num_hidden_layers, num_envs, kv_heads(cfg), capacity, cfg.head_dim)
    return {"k": jnp.zeros(shape, jnp.float32), "v": jnp.zeros(shape, jnp.float32), "pos": jnp.zeros((), jnp.int32),
            "start": jnp.zeros((num_envs,), jnp.int32)}


def forward_decode(params: Params, cache: Dict[str, jax.Array], ids: jax.Array, positions: jax.Array, own: jax.Array, base: jax.Array,
                   start: jax.Array, cfg: SequenceConfig, mask_id: int, write: int = 0):
    """`ids` [N, R] rows an env at `positions` [R] through the cache: every row attends to its env's cached rows
    `start[e] .. base - 1` and, under `own` [R, R], to the rows of this call. The first `write` rows' keys and values
    are written at `base ..` (in place, after they were read). Returns (logits [N, R, vocab_held], values [N, R], the
    cache with its keys and values; `pos` and `start` as they were)."""
    inv_freq = yarn_inv_freq(cfg)
    N, R = ids.shape
    keys, vals = cache["k"], cache["v"]
    D, KV = cfg.head_dim, kv_heads(cfg)
    at = jnp.arange(keys.shape[3])
    live = ((at >= start[:, None]) & (at < base))[:, None, :]  # [N, 1, P]
    with jax.named_scope("embed"):
        X = params["embed"]["embedding"][ids]
    for i in range(cfg.num_hidden_layers):
        layer = params[f"layer_{i}"]
        with jax.named_scope("bd_attn"):
            u = rms_norm(X, layer["attn_norm"]["scale"], cfg.rms_norm_eps)
            q, k, v = project(layer["attn"], u, positions, cfg, inv_freq)
            cached = (jnp.swapaxes(keys[i], 1, 2), jnp.swapaxes(vals[i], 1, 2), live)
            o = attend(q, [cached, (k, v, own[None])], cfg)
            if write:
                keys = jax.lax.dynamic_update_slice(keys, jnp.swapaxes(k[None, :, :write], 2, 3), (i, 0, 0, base, 0))
                vals = jax.lax.dynamic_update_slice(vals, jnp.swapaxes(v[None, :, :write], 2, 3), (i, 0, 0, base, 0))
            X = X + o @ layer["attn"]["w_o"]["kernel"]
        X, _ = _ffn(layer, X, cfg)
    with jax.named_scope("head"):
        return _head(params, X, cfg, mask_id), _value(params, X, cfg), {**cache, "k": keys, "v": vals}
