"""The recurrent on-policy loop with a block-diffusion model as its backbone
(`algos/ppo_recurrent/block_diffusion_policy.py`, driven by the loop of
`sequence_policy.main`; `exp=ppo_recurrent_sdar`) behind the harness's seam:
an env step commits a block of `L` ids in `S` denoising forwards through a
grouped-query K/V cache.

Two names of the loop's modules and one of the run around it (`loop.py`) are
wrapped for the length of one run:

* ``build_agent`` (of `sequence_policy`): the parameters it returns are the
  benchmark's own seeded weights, through its own `params` argument;
* ``make_update_fn`` (of `block_diffusion_policy`): the returned ``update``
  (all epochs and minibatches of one rollout, one jitted program) is the train
  call; G = update epochs x minibatches. During set-up the wrapper keeps host
  copies of what the first call was given and gave back;
* ``RunGuard``: see `taps.Run.wrap_guard`.

What `decide` compares, at the timed sizes, of what the timed path produced,
each stage against the plain float32 reference (`references/` beside this
file, of this adapter's name) on the same seeded weights:

(a) every row of the first rollout (observed block, action block, reward, end,
    episode start) against the generator's log, exact;
(b) the log-probabilities and values the player stored while denoising through
    the cache, two forwards a block, against the reference's one full forward
    over the rollout's concatenated layout (clean blocks and every copy, from
    the rollout's tokens, actions and `order`): the 95th percentile over the
    env steps (`*_gap`) and the worst one (`*_worst`);
(c) the advantages, from the reference's own values;
(d) the three losses of the first minibatch and the parameters' change over
    the first update, by the worst leaf and by the median leaf, the
    reference's update fed the rollout as the program stored it;
(e) env steps against what the updates owe, structural; (f) no routed pair
    dropped.

A routing choice can flip on rounding between the program's bfloat16 operands
and the reference's float32 (the eighth and ninth of 128 softmax scores can lie
a rounding apart), which moves that token's numbers by one expert's output:
that is what the 95th percentiles leave out and the worst readings hold.

`calibrate` sides: control_fp8 (the reference with every matmul operand
rounded to float8_e4m3fn, the precision below the bfloat16 operands the
configuration states), bf16_operands (no control: what the chip's default
precision does), and one fault_<kind> for each kind of `REFERENCE_FAULTS`.
"""
from __future__ import annotations

import contextlib
import time
from functools import partial
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

from ..check import gap_by_leaf
from ..reference import make_weights, nest
from ..references import ppo_recurrent_diffusion as reference
from ..taps import Run, flat_names

CHECK_CALLS = 1  # the reference follows the first update: the only rollout acted with the seeded weights
step_programs = ("jit_update",)  # the device program that is the train step
decode_programs = ("jit_act",)  # the player's env step: S denoising forwards through the cache
# the `jax.named_scope`s of both programs, outermost level; `experts` lies inside `moe` and holds the grouped products alone
step_parts = ("embed", "bd_attn", "moe", "experts", "head", "loss", "optimizer")
# the CPU rehearsal only: widths a CPU compiles in seconds, every mechanism on
rehearsal_overrides = ["algo.backbone.hidden_size=64", "algo.backbone.head_dim=16", "algo.backbone.num_attention_heads=8",
                       "algo.backbone.moe_intermediate_size=32", "algo.backbone.num_hidden_layers=2"]
compared_numbers = frozenset({
    "rollout_wrong_rows", "update_early_steps", "update_late_steps", "values_gap", "logprobs_gap", "values_worst", "logprobs_worst",
    "advantages_gap", "loss_gap_policy", "loss_gap_value", "loss_gap_entropy", "update_gap", "update_mid", "moe_dropped"})
# per planted fault, the numbers of which one has to fail
fault_kinds = {
    "unchanged": ("update_gap",),             # an update that leaves its parameters as they were
    "half_steps": ("update_mid",),            # half of each epoch's minibatches skipped
    "half_batch": ("update_mid", "loss_gap"),  # half of each minibatch's sequences left out, the means taken over the rest
    "causal_block": ("logprobs",),            # a copy attends causally within its block, acting and updating
    "one_copy": ("loss_gap", "update"),       # the update takes every position's log-probability from copy 0
}
DATA = ("tokens", "actions", "order", "is_first", "logprobs", "values", "returns", "advantages")  # what the update reads of a rollout


# -- the taps ------------------------------------------------------------------------
def seed_weights(run: Run, orig: Callable) -> Callable:
    def build_agent(dist, cfg, observation_space, action_space, key, params=None):
        import jax

        run.cfg = cfg
        params = run.seeded(jax.eval_shape(lambda k: orig(dist, cfg, observation_space, action_space, k)[1], key), dist.local_device)
        return orig(dist, cfg, observation_space, action_space, key, params)

    return build_agent


def _timed(run: Run, update: Callable, g: int) -> Callable:
    def timed_update(params, opt_state, data, coefs, key):
        import jax

        checking = len(run.calls_t) < CHECK_CALLS
        if checking:
            t0 = time.perf_counter()
            run.rollout = {k: np.asarray(v) for k, v in data.items()}
            run.coefs = {k: float(v) for k, v in coefs.items()}
            run.update_key = np.asarray(jax.random.key_data(key) if jax.dtypes.issubdtype(key.dtype, jax.dtypes.prng_key) else key)
            run.check_s += time.perf_counter() - t0
        run.before_call()
        out = update(params, opt_state, data, coefs, key)
        if checking:
            t0 = time.perf_counter()
            run.params_after = {k: np.asarray(v) for k, v in flat_names(out[0]).items()}
            run.first_losses = {k: float(np.asarray(v)[0, 0]) for k, v in out[3]["losses"].items()}
            run.check_s += time.perf_counter() - t0
        run.loads.append(out[3]["load"]["dropped"])  # device arrays: read after the window, no fetch inside it
        run.stamp(g, out[0])
        return out

    return timed_update


def _modules():
    from sheeprl_tpu.algos.ppo_recurrent import block_diffusion_policy, sequence_policy

    return sequence_policy, block_diffusion_policy


def installed(run: Run):
    """The context manager that hangs the taps on the program for one run."""
    run.warmup_calls = max(run.warmup_calls, CHECK_CALLS + 1)
    run.rollout = run.coefs = run.update_key = run.params_after = run.first_losses = None
    run.loads = []

    def wrap_make_update_fn(orig: Callable) -> Callable:
        def make_update_fn(module, tx, cfg, num_minibatches, mb_size):
            run.notes["minibatches"], run.notes["minibatch_seqs"] = int(num_minibatches), int(mb_size)
            return _timed(run, orig(module, tx, cfg, num_minibatches, mb_size), int(cfg.algo.update_epochs) * int(num_minibatches))

        return make_update_fn

    from sheeprl_tpu.algos.ppo_recurrent import loop

    loop_module, policy = _modules()
    stack = contextlib.ExitStack()
    stack.enter_context(run.patched(loop, {"RunGuard": run.wrap_guard}))  # the run around the loop sets the guard up
    stack.enter_context(run.patched(loop_module, {"build_agent": partial(seed_weights, run)}))
    stack.enter_context(run.patched(policy, {"make_update_fn": wrap_make_update_fn}))
    return stack


# -- shapes, sizes, work -------------------------------------------------------------
def _spaces(mix: Dict[str, Any]):
    import gymnasium as gym

    (key, spec), = mix["observation"].items()
    n, L = int(mix["action"]["n"]), int(spec["shape"][0])
    return gym.spaces.Dict({key: gym.spaces.Box(0, n - 1, (L,), np.dtype(spec["dtype"]))}), gym.spaces.MultiDiscrete([n] * L)


def _composed(spec: Dict[str, Any], rehearse: bool = False):
    from sheeprl_tpu.config import compose

    from ..run import overrides_for

    return compose("config", overrides_for(spec, 0, rehearse))


def sizes_for(cfg: Any, minibatches: int, minibatch_seqs: int) -> reference.Sizes:
    """What the reference needs of a cell, from the composed config the program runs with."""
    a, b = cfg.algo, cfg.algo.backbone
    if str(a.loss_reduction) != "mean" or bool(a.anneal_lr) or float(a.optimizer.get("weight_decay", 0)) != 0:
        raise ValueError("the reference takes means, a fixed learning rate and an Adam without weight decay")
    heads = int(b.heads_held)
    return reference.Sizes(
        heads=heads, kv_heads=int(b.num_key_value_heads) * heads // int(b.num_attention_heads), head_dim=int(b.head_dim),
        experts=int(b.n_routed_experts), top_k=int(b.num_experts_per_tok), experts_held=int(b.experts_held),
        first_expert=int(b.first_expert), layers=int(b.num_hidden_layers), theta=float(b.rope_theta), norm_eps=float(b.rms_norm_eps),
        block=int(b.block_length), steps=int(b.denoising_steps), mask_id=int(b.vocab_held) - 1, envs=int(cfg.env.num_envs),
        blocks=int(a.rollout_steps), gamma=float(a.gamma), gae_lambda=float(a.gae_lambda), epochs=int(a.update_epochs),
        minibatches=int(minibatches), minibatch_seqs=int(minibatch_seqs), normalize_advantages=bool(a.normalize_advantages),
        clip_vloss=bool(a.clip_vloss), lr=float(a.optimizer.lr), eps=float(a.optimizer.eps),
        max_grad_norm=float(a.get("max_grad_norm", 0.0) or 0.0),
    )


WIDTHS = ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "moe_intermediate_size", "num_experts_per_tok",
          "block_length", "denoising_steps")


def widths_of(cfg: Any) -> Dict[str, int]:
    """The keys of a configuration file's `widths`, from the composed config the program runs with: every published
    width, the router's width, the block and its denoising steps, and the sizes of a rollout and of a minibatch."""
    a, b = cfg.algo, cfg.algo.backbone
    out = {k: int(b[k]) for k in WIDTHS}
    out.update(router_width=int(b.n_routed_experts), rollout_steps=int(a.rollout_steps), update_epochs=int(a.update_epochs),
               minibatches=int(a.per_rank_num_batches), minibatch_sequences=int(cfg.env.num_envs) // int(a.per_rank_num_batches))
    return out


def program_shapes(spec: Dict[str, Any], rehearse: bool = False) -> Tuple[Any, Dict[str, Tuple[Tuple[int, ...], Any]]]:
    """(composed config, {leaf name: (shape, dtype)}) of a cell, by `jax.eval_shape` over the program's own `build_agent`."""
    import jax

    from sheeprl_tpu.algos.ppo_recurrent.agent import build_agent
    from sheeprl_tpu.parallel import Distributed

    cfg = _composed(spec, rehearse)
    obs_space, action_space = _spaces(spec["mix"])
    tree = jax.eval_shape(lambda k: build_agent(Distributed(devices=1), cfg, obs_space, action_space, k)[1], jax.random.key(0))
    return cfg, {n: (tuple(x.shape), np.dtype(x.dtype)) for n, x in flat_names(tree).items()}


def mean_context(mix: Dict[str, Any]) -> float:
    """Blocks of its own episode an observed block attends to, itself included, on average over a rollout of the mix:
    an episode ends at each inner boundary of `episode_unit` blocks with probability `cut_share`."""
    unit, units, keep = int(mix["episode_unit"]), int(mix["rollout_steps"]) // int(mix["episode_unit"]), 1.0 - float(mix["cut_share"])
    whole_units = np.mean([sum(keep ** i for i in range(1, j + 1)) for j in range(units)])  # uncut units behind a block's own
    return (unit + 1) / 2.0 + unit * float(whole_units)


def _sizes(shapes: Dict[str, Any], spec: Dict[str, Any]) -> Dict[str, float]:
    """Multiply-adds of one token through one layer's parts (the experts by the routing's expected load: top-k x held /
    routed of a token's pairs come here), and the counts the work functions share."""
    w = spec["config"]["widths"]
    layers = len({n.split("/")[0] for n in shapes if n.startswith("layer_")})
    size = lambda keep: sum(float(np.prod(s)) for n, (s, _) in shapes.items() if keep(n) and n.endswith("/kernel"))  # noqa: E731
    held = next(s[0] for n, (s, _) in shapes.items() if n.endswith("moe/experts/w_gate/kernel"))
    share = float(w["num_experts_per_tok"]) * held / float(w["router_width"])
    return {"layers": layers, "attn": size(lambda n: "/attn/" in n) / layers, "router": size(lambda n: "/moe/router/" in n) / layers,
            "experts": size(lambda n: "/moe/experts/" in n) / layers / held * share, "head": float(np.prod(shapes["head/kernel"][0])),
            "value": float(np.prod(shapes["value/kernel"][0])), "heads": float(w["num_attention_heads"]), "D": float(w["head_dim"]),
            "L": float(w["block_length"]), "S": float(w["denoising_steps"]), "T": float(w["rollout_steps"]),
            "seqs": float(w["minibatch_sequences"]), "ctx": mean_context(spec["mix"])}


def _attn_pairs(z: Dict[str, float]) -> float:
    """Unmasked (query, key) pairs of one sequence's update forward: a clean row sees its context's blocks; a copy row
    those and its own copy."""
    L, S, T, ctx = z["L"], z["S"], z["T"], z["ctx"]
    return T * L * L * ctx + T * S * L * (L * ctx + L)


def _decode_pairs(z: Dict[str, float]) -> float:
    """Pairs of one env step's forwards: forward 1's observed rows see the context and themselves, its masked rows
    those and themselves; each later forward's rows the context (now with the observed block) and themselves."""
    L, S, ctx = z["L"], z["S"], z["ctx"]
    return L * L * ctx + L * (L * ctx + L) + (S - 1) * L * (L * ctx + L)


def step_flops(shapes: Dict[str, Any], spec: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs of one gradient step under 'total' (a minibatch of whole sequences, each its clean blocks and S copies of
    every action block through every layer, the head at each action position and the value at each block; forward 2
    FLOP a multiply-add, backward twice the forward) and of the player's S forwards for one env step under
    'per_env_step' (2 L rows, then L rows S - 1 times). An on-policy loop acts for every block it trains on."""
    z = _sizes(shapes, spec)
    per_token = z["layers"] * (z["attn"] + z["router"] + z["experts"])
    attend = z["layers"] * z["heads"] * 2 * z["D"]  # a pair's score and weighted value
    seq = (1 + z["S"]) * z["T"] * z["L"] * per_token + _attn_pairs(z) * attend + z["T"] * (z["L"] * z["head"] + z["value"])
    rows = (1 + z["S"]) * z["L"]
    act = rows * per_token + _decode_pairs(z) * attend + z["S"] * z["L"] * z["head"] + z["value"]
    return {"total": 6.0 * seq * z["seqs"], "per_env_step": 2.0 * act}


def bd_attn_flops(shapes: Dict[str, Any], spec: Dict[str, Any]) -> float:
    """FLOPs of the two-part attention of one gradient step, forward and backward: its projections (q, k, v, o) for
    every row and the unmasked (query, key) pairs' scores and weighted values. Remat's recompute is not counted."""
    z = _sizes(shapes, spec)
    rows = (1 + z["S"]) * z["T"] * z["L"]
    return 6.0 * z["seqs"] * z["layers"] * (rows * z["attn"] + _attn_pairs(z) * z["heads"] * 2 * z["D"])


def bd_attn_bytes(shapes: Dict[str, Any], spec: Dict[str, Any]) -> float:
    """Bytes the two-part attention of one gradient step must move: its kernels read forward, read again for the
    input's gradient and their gradient written (3 x), and per layer the rows' input and output read and written
    forward and twice backward (3 x 2 x rows x hidden, float32)."""
    z = _sizes(shapes, spec)
    kernels = sum(float(np.prod(s)) * np.dtype(d).itemsize for n, (s, d) in shapes.items() if "/attn/" in n)
    rows = (1 + z["S"]) * z["T"] * z["L"] * z["seqs"]
    return 3.0 * kernels + 3.0 * 2.0 * rows * int(spec["config"]["widths"]["hidden_size"]) * 4.0 * z["layers"]


def expert_flops(shapes: Dict[str, Any], spec: Dict[str, Any], pairs: float) -> float:
    """FLOPs of the grouped expert products, forward and backward, for `pairs` (token, expert) pairs: three kernels a pair."""
    e = next(s for n, (s, _) in shapes.items() if n.endswith("moe/experts/w_gate/kernel"))
    return 6.0 * 3.0 * e[1] * e[2] * pairs


def expert_bytes(shapes: Dict[str, Any]) -> float:
    """Bytes of the held experts' kernels, all layers: what the grouped products of one pass read."""
    return sum(float(np.prod(s)) * np.dtype(d).itemsize for n, (s, d) in shapes.items() if "/moe/experts/" in n)


def decode_bytes(shapes: Dict[str, Any], spec: Dict[str, Any]) -> float:
    """Bytes one env step's S forwards for all envs must read: each forward every parameter it touches once (the
    embedding's rows of its ids; every held expert, since at `envs` x 2 L rows a held expert goes unchosen with
    probability (1 - k / routed)^(envs L), under 3e-4 in the cell) and the cache rows in use (keys and values of
    every layer)."""
    envs, w = int(spec["mix"]["num_envs"]), spec["config"]["widths"]
    z = _sizes(shapes, spec)
    params = sum(float(np.prod(s)) * np.dtype(d).itemsize for n, (s, d) in shapes.items() if not n.startswith("embed/"))
    rows = envs * z["L"] * (1 + z["S"]) * int(w["hidden_size"]) * 4.0
    kv = 2.0 * int(w["num_key_value_heads"]) * int(w["head_dim"]) * 4.0
    return z["S"] * params + rows + z["S"] * envs * z["layers"] * z["L"] * z["ctx"] * kv


def kept_bytes(shapes: Dict[str, Any], spec: Dict[str, Any]) -> Dict[str, float]:
    """What the cell keeps on the chip across calls: parameters, Adam's two moments and the per-env K/V cache."""
    params = float(sum(int(np.prod(s)) * np.dtype(d).itemsize for s, d in shapes.values()))
    w = spec["config"]["widths"]
    layers = len({n.split("/")[0] for n in shapes if n.startswith("layer_")})
    tokens = int(w["rollout_steps"]) * int(w["block_length"])
    cache = 4.0 * 2 * layers * int(spec["mix"]["num_envs"]) * tokens * int(w["num_key_value_heads"]) * int(w["head_dim"])
    return {"params": params, "adam": 2.0 * params, "cache": cache, "total": 3.0 * params + cache}


def rehearse(spec: Dict[str, Any], topology: Any) -> Dict[str, Any]:
    """Compile the env step's decode and the whole update for a described chip (none attached) at the cell's real
    sizes: what each needs, and the worst case (the update's arguments, which are the kept state, its temp, the cache)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from sheeprl_tpu.algos.ppo_recurrent.agent import build_agent
    from sheeprl_tpu.config import instantiate
    from sheeprl_tpu.optim import clipped
    from sheeprl_tpu.parallel import Distributed

    one_chip = SingleDeviceSharding(topology.devices[0])

    def like(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)

    _, policy = _modules()
    cfg = _composed(spec)
    obs_space, action_space = _spaces(spec["mix"])
    made = {}

    def build(key):
        made["module"], params = build_agent(Distributed(devices=1), cfg, obs_space, action_space, key)
        return params

    params = jax.eval_shape(build, jax.random.key(0))
    tx = clipped(instantiate(cfg.algo.optimizer), cfg.algo.get("max_grad_norm", 0.0))
    opt_state = jax.eval_shape(tx.init, params)
    envs, steps, batches = int(cfg.env.num_envs), int(cfg.algo.rollout_steps), int(cfg.algo.per_rank_num_batches)
    L = made["module"].block_length
    key = like(jax.eval_shape(lambda: jax.random.key(0)))
    arr = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    out: Dict[str, Any] = {}
    t0 = time.time()
    state = like(jax.eval_shape(lambda: policy.new_state(made["module"], envs, steps)))
    act = policy.make_act_fn(made["module"]).lower(like(params), state, arr(jnp.int32, envs, L), arr(jnp.bool_, envs), key).compile()
    mem = act.memory_analysis()
    out["act"] = {"args_gb": mem.argument_size_in_bytes / 1e9, "temp_gb": mem.temp_size_in_bytes / 1e9, "compile_s": time.time() - t0}
    t0 = time.time()
    blocks = ("tokens", "actions", "order")
    data = {k: arr(jnp.int32 if k in blocks else jnp.bool_ if k == "is_first" else jnp.float32, envs, steps, *((L,) if k in blocks else ()))
            for k in DATA + ("rewards", "dones")}
    coefs = {k: arr(jnp.float32) for k in ("clip_coef", "ent_coef", "vf_coef", "lr_frac")}
    update = policy.make_update_fn(made["module"], tx, cfg, batches, envs // batches).lower(like(params), like(opt_state), data, coefs, key).compile()
    mem = update.memory_analysis()
    shapes = {k: (x.shape, x.dtype) for k, x in flat_names(params).items()}
    out["update"] = {"args_gb": mem.argument_size_in_bytes / 1e9, "temp_gb": mem.temp_size_in_bytes / 1e9,
                     "code_gb": mem.generated_code_size_in_bytes / 1e9, "compile_s": time.time() - t0}
    out["worst_case_gb"] = (mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes) / 1e9 + kept_bytes(shapes, spec)["cache"] / 1e9
    out["flops_per_grad_step"] = step_flops(shapes, spec)
    return out


# -- the first update against the reference -------------------------------------------------
def rollout_rows(rollout: Dict[str, np.ndarray], envs: Dict[int, Any], sz: reference.Sizes) -> Tuple[int, int]:
    """(rows looked at, rows that differ from what the generator emitted). `rollout` is sequence-major [envs, T, ...];
    step t of env e is the t-th emission of that env that is not a final observation."""
    rows = wrong = 0
    for e in range(sz.envs):
        env = envs.get(e)
        seen = env.seen() if env is not None else []
        for t in range(sz.blocks):
            rows += 1
            if t >= len(seen) or seen[t] + 1 >= env.n:
                wrong += 1
                continue
            n = seen[t]
            ok = (
                list(rollout["tokens"][e, t]) == list(env.log_block[n]) and list(rollout["actions"][e, t]) == list(env.log_action[n])
                # the step that answered emission n emitted n + 1: its reward and whether it closed an episode
                and float(rollout["rewards"][e, t]) == np.float32(env.log_reward[n + 1])
                and float(rollout["dones"][e, t]) == float(env.log_final[n + 1])
                and bool(rollout["is_first"][e, t]) == bool(env.log_first[n] or t == 0)
            )
            wrong += 0 if ok else 1
    return rows, wrong


def rollout_from_generator(spec: Dict[str, Any], seed: int, sz: reference.Sizes) -> Dict[str, np.ndarray]:
    """One rollout [envs, T, ...] from the cell's generator alone, the actions and orders drawn from the seed (never
    the mask id): the rows a calibration needs, of the kind `rollout_rows` accepts."""
    from ..envs import generator_of

    cls = generator_of(spec["mix"])
    (key, _), = spec["mix"]["observation"].items()
    L, S = sz.block, sz.steps
    out = {k: np.zeros((sz.envs, sz.blocks, L), np.int32) for k in ("tokens", "actions", "order")}
    out.update({k: np.zeros((sz.envs, sz.blocks), d) for k, d in (("rewards", np.float32), ("dones", np.float32), ("is_first", bool))})
    for e in range(sz.envs):
        env = cls(mix=spec["mix_ref"], seed=0, rank=e, bench_seed=seed)
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, e, 9])
        obs, first = env.reset()[0], True
        for t in range(sz.blocks):
            action = rng.integers(0, sz.mask_id, L)
            out["tokens"][e, t], out["actions"][e, t], out["is_first"][e, t] = obs[key], action, first or t == 0
            out["order"][e, t] = np.argsort(rng.random(L)).argsort() // (L // S)
            obs, reward, term, trunc, _ = env.step(action)
            out["rewards"][e, t], out["dones"][e, t], first = reward, float(term or trunc), bool(term or trunc)
            if first:
                obs = env.reset()[0]
    return out


def delta_norms(after: Dict[str, Any], before: Dict[str, Any]) -> Dict[str, float]:
    import jax.numpy as jnp

    return {k: float(jnp.sqrt(jnp.sum(jnp.square(jnp.asarray(after[k]) - before[k])))) for k in before}


# the planted faults the reference can take: (coefs, sizes) of the broken update
REFERENCE_FAULTS = {
    "unchanged": lambda coefs, sz: ({**coefs, "lr_frac": 0.0}, sz),
    "half_steps": lambda coefs, sz: (coefs, sz._replace(minibatches=sz.minibatches // 2)),
    "half_batch": lambda coefs, sz: (coefs, sz._replace(minibatch_seqs=sz.minibatch_seqs // 2)),
}


def reference_side(seed: int, shapes: Dict[str, Any], rollout: Dict[str, np.ndarray], coefs: Dict[str, float], key_data: np.ndarray,
                   sz: reference.Sizes, od: Any = None, stored: Dict[str, np.ndarray] = None, fault: str = "") -> Dict[str, Any]:
    """What a run is compared by, from the reference at operand type `od` on the seeded weights: the log-probabilities
    and values of the rollout, the advantages, and the first update's losses and change by leaf. The update is fed
    `stored` (the rollout as a program kept it) where given, else this side's own numbers, and is broken as
    `REFERENCE_FAULTS[fault]` says where a fault is named."""
    import jax.numpy as jnp

    weights = make_weights(seed, shapes)
    logprobs, values = reference.rollout_forward(nest(dict(weights)), rollout, sz, od)
    adv = reference.gae(jnp.asarray(rollout["rewards"]).T, values.T, jnp.asarray(rollout["dones"]).T, sz).T
    side = {"logprobs": np.asarray(logprobs), "values": np.asarray(values), "advantages": np.asarray(adv)}
    data = dict(stored) if stored is not None else {**rollout, "logprobs": side["logprobs"], "values": side["values"],
                                                    "advantages": side["advantages"], "returns": side["advantages"] + side["values"]}
    if fault:
        coefs, sz = REFERENCE_FAULTS[fault](coefs, sz)
    after, steps = reference.first_update(weights, {k: data[k] for k in DATA}, coefs, key_data, sz, od)
    side["losses"] = steps[0]
    side["delta"] = delta_norms(after, make_weights(seed, shapes))
    return side


def compare_sides(side: Dict[str, Any], ref: Dict[str, Any]) -> Tuple[Dict[str, float], Dict[str, Any]]:
    """A side (the program's numbers, or the reference's at another operand type) against the reference."""
    values: Dict[str, float] = {}
    for name in ("values", "logprobs", "advantages"):
        gap = np.abs(side[name] - ref[name]) / (float(np.median(np.abs(ref[name]))) + 1e-6)
        values[f"{name}_gap"] = float(np.quantile(gap, 0.95))
        if name != "advantages":
            values[f"{name}_worst"] = float(np.max(gap))
    for name, want in ref["losses"].items():
        values["loss_gap_" + name.split("/")[1].replace("_loss", "")] = abs(side["losses"][name] - want) / (abs(want) + 0.1)
    values["update_gap"], values["update_mid"], worst = gap_by_leaf(side["delta"], ref["delta"])
    return values, {"worst_leaf": worst, "losses_side_reference": {k: [side["losses"][k], v] for k, v in ref["losses"].items()}}


def decide(run: Run, envs: Dict[int, Any], spec: Dict[str, Any]) -> Tuple[Dict[str, float], Dict[str, float], Dict[str, Any]]:
    """(every number read, the limits the cell's structure gives, the detail)."""
    sz = sizes_for(run.cfg, run.notes["minibatches"], run.notes["minibatch_seqs"])
    values: Dict[str, float] = {}
    detail: Dict[str, Any] = {}

    rows, wrong = rollout_rows(run.rollout, envs, sz)
    detail["rollout"] = {"rows": rows, "wrong_rows": wrong}
    values["rollout_wrong_rows"] = float(wrong)
    values["moe_dropped"] = float(sum(int(np.sum(np.asarray(x))) for x in run.loads))

    # an update is owed every blocks x envs env steps: never before they were taken, and the loop is serial
    per_update = sz.blocks * sz.envs
    exits = np.sort(np.concatenate([np.asarray(e.t_exit) for e in envs.values()]))
    enters = np.sort(np.concatenate([np.asarray(e.t_enter) for e in envs.values()]))
    calls_t = np.asarray(run.calls_t)
    owed = per_update * np.arange(1, len(calls_t) + 1)
    values["update_early_steps"] = float(np.max(owed - np.searchsorted(exits, calls_t, side="right")))
    values["update_late_steps"] = float(np.max(np.searchsorted(enters, calls_t, side="right") - owed))
    structural = {"rollout_wrong_rows": 0.0, "update_early_steps": 0.0, "update_late_steps": 0.0, "moe_dropped": 0.0}

    if wrong == 0:
        t0 = time.perf_counter()
        before = make_weights(run.seed, run.shapes)
        side = {k: run.rollout[k] for k in ("logprobs", "values", "advantages")}
        side["delta"] = delta_norms(run.params_after, before)
        del before
        side["losses"] = run.first_losses
        ref = reference_side(run.seed, run.shapes, run.rollout, run.coefs, run.update_key, sz, stored=run.rollout)
        compared, more = compare_sides(side, ref)
        values.update(compared)
        detail.update(more)
        detail["reference_s"] = time.perf_counter() - t0
    return values, structural, detail


# -- the readings the limits are set from (perfbench/calibrate.py) -------------------------------
def calibrate(spec: Dict[str, Any], seeds: List[int], only=None, rehearse: bool = False):
    """Per seed, the control and the planted faults the reference can take (the module's docstring lists the sides),
    each put in the program's place and compared with the plain reference by the same numbers as a run, at the cell's
    own sizes, on a rollout from the cell's own generator."""
    import jax
    import jax.numpy as jnp

    cfg, shapes = program_shapes(spec, rehearse)
    batches = int(cfg.algo.per_rank_num_batches)
    sz = sizes_for(cfg, batches, int(cfg.env.num_envs) // batches)
    coefs = {"clip_coef": float(cfg.algo.clip_coef), "ent_coef": float(cfg.algo.ent_coef), "vf_coef": float(cfg.algo.vf_coef), "lr_frac": 1.0}
    sides = {"control_fp8": {"od": jnp.float8_e4m3fn}, "bf16_operands": {"od": jnp.bfloat16},
             **{f"fault_{kind}": {"fault": kind} for kind in REFERENCE_FAULTS}}
    sides = {k: v for k, v in sides.items() if only is None or k in only}
    for seed in seeds:
        t0 = time.time()
        rollout = rollout_from_generator(spec, seed, sz)
        key = np.asarray(jax.random.key_data(jax.random.key(seed % 2147483647)))
        ref = reference_side(seed, shapes, rollout, coefs, key, sz)
        rec: Dict[str, Any] = {"seed": seed}
        for name, kw in sides.items():
            rec[name], _ = compare_sides(reference_side(seed, shapes, rollout, coefs, key, sz, **kw), ref)
        rec["seconds"] = time.time() - t0
        yield rec


# -- the faults the tests plant (never used by a benchmark run) -----------------------------
@contextlib.contextmanager
def faults(kind: str):
    """The timed path broken beneath the harness's own wrappers. `unchanged`: an update whose every step is scaled
    to nothing. `half_steps`: each epoch takes half of its minibatches. `half_batch`: every minibatch holds half of
    its sequences. `causal_block`: a denoising copy attends causally within its block, in acting and in the update.
    `one_copy`: the update reads every action position's log-probability from copy 0, the fully masked one."""
    if kind not in fault_kinds:
        raise ValueError(f"unknown fault {kind!r}")
    from sheeprl_tpu.models import block_diffusion as bd

    _, policy = _modules()
    orig = policy.make_update_fn
    saved = {"copy_mask": bd.copy_mask, "pick_copies": bd.pick_copies}

    def make_update_fn(module, tx, cfg, num_minibatches, mb_size):
        if kind == "half_steps":
            return orig(module, tx, cfg, num_minibatches // 2, mb_size)
        if kind == "half_batch":
            return orig(module, tx, cfg, num_minibatches, mb_size // 2)
        update = orig(module, tx, cfg, num_minibatches, mb_size)
        if kind != "unchanged":
            return update

        def unchanged(params, opt_state, data, coefs, key):
            return update(params, opt_state, data, {**coefs, "lr_frac": coefs["lr_frac"] * 0.0}, key)

        return unchanged

    import jax.numpy as jnp

    policy.make_update_fn = make_update_fn
    if kind == "causal_block":
        bd.copy_mask = lambda L: jnp.tril(jnp.ones((L, L), bool))
    if kind == "one_copy":
        bd.pick_copies = lambda copies, order: saved["pick_copies"](copies, jnp.zeros_like(order))
    try:
        yield
    finally:
        policy.make_update_fn = orig
        bd.copy_mask, bd.pick_copies = saved["copy_mask"], saved["pick_copies"]
