"""The recurrent on-policy loop with a sequence model as its backbone
(`algos/ppo_recurrent/sequence_policy.py`, `exp=ppo_recurrent_xing4`) behind
the harness's seam: `perfbench/adapters/ppo_recurrent_sequence.py`, named
after the algorithm and its loop's module. (Not the algorithm's bare name:
the rehearsal of `tests/perfbench/pb_rehearsal.py`, which no PR of this kind
may edit, writes a reader that names that algorithm as one WITHOUT an
adapter, and the test that an algorithm is named by its adapter and its
reference alone then fails over the rehearsed tree for an adapter so named.)

Two names of the loop's module and one of the run around it (`loop.py`) are
wrapped for the length of one run:

* ``build_agent``: the parameters it returns are the benchmark's own seeded
  weights, through its own `params` argument (the resume path);
* ``make_update_fn``: the returned ``update`` (all epochs and minibatches of
  one rollout, one jitted program) is the train call; G = update epochs x
  minibatches. During set-up the wrapper keeps host copies of what the first
  call was given (the whole rollout, the coefficients, the key) and gave back
  (the parameters, the losses of every gradient step);
* ``RunGuard``: see `taps.Run.wrap_guard`.

What `decide` compares, at the timed sizes, of what the timed path produced,
each stage against the plain float32 reference (`references/ppo_recurrent_sequence.py`)
on the same seeded weights:

(a) every row of the first rollout against the generator's log, exact;
(b) the log-probabilities and values the player stored while decoding through
    the latent cache against the reference's full uncached forward over the
    same tokens: the cache against the full forward;
(c) the advantages, from the reference's own values;
(d) the three losses of the first minibatch and the parameters' change over
    the first update by the worst leaf, the reference's update fed with the
    rollout as the program stored it, so that (d) reads the update alone;
(e) env steps against what the updates owe, structural.

A routing choice can flip on rounding between the program's bfloat16 operands
and the reference's float32 (a token's fourth and fifth expert lie a rounding
apart): ``routing_flips`` is the share of (token, expert layer) pairs whose
chosen sets differ between the program's own full forward at its precision
and the reference, with a limit of its own. A flipped pair moves that token's
numbers by one expert's output, and through the attention a little of every
later token of its episode, so (b) and (c) read the 95th percentile over the
tokens ((b): over those whose routing agreed in every layer); the worst token
of (b), over ALL tokens, is compared beside them under a limit of its own
(``values_worst``, ``logprobs_worst``), so that a fault on a few tokens, as
the first after a restart of the cache would be, does not hide under the 95 %.

The update is read twice: ``update_gap`` is the worst leaf's norm of change
against the reference's, ``update_mid`` the median leaf's. Adam moves an
element by about the learning rate a step whatever the gradient's size, so a
leaf's norm of change counts the steps taken and follows how steadily the
gradients pointed one way: sound runs agree with the reference to two
thousandths by the median leaf, and an update that took half of its gradient
steps, or each from half of its minibatch's sequences, does not (`faults`).

`calibrate` sides: control_fp8 (the reference with every matmul operand
rounded to float8_e4m3fn, the precision below the bfloat16 operands the
configuration states), bf16_operands (no control: what the chip's default
precision does), and one fault_<kind> for each kind of `REFERENCE_FAULTS`.
"""
from __future__ import annotations

import contextlib
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..check import gap_by_leaf
from ..reference import make_weights, nest
from ..references import ppo_recurrent_sequence as reference
from ..taps import Run, flat_names

CHECK_CALLS = 1  # the reference follows the first update: the only rollout acted with the seeded weights
step_programs = ("jit_update",)  # the device program that is the train step
decode_programs = ("jit_act",)  # the player's one-token step through the cache
# the `jax.named_scope`s of both programs, outermost level; `experts` lies inside `moe` and holds the grouped products alone
step_parts = ("embed", "mla", "moe", "experts", "dense_mlp", "mhc", "head", "loss", "optimizer")
# the CPU rehearsal only: widths a CPU compiles in seconds, every mechanism on
rehearsal_overrides = [
    "algo.backbone.hidden_size=64", "algo.backbone.q_lora_rank=24", "algo.backbone.kv_lora_rank=16",
    "algo.backbone.qk_nope_head_dim=8", "algo.backbone.qk_rope_head_dim=8", "algo.backbone.v_head_dim=8",
    "algo.backbone.intermediate_size=96", "algo.backbone.moe_intermediate_size=32", "algo.backbone.num_hidden_layers=3",
]
compared_numbers = frozenset({
    "rollout_wrong_rows", "update_early_steps", "update_late_steps", "routing_flips", "values_gap", "logprobs_gap", "advantages_gap",
    "values_worst", "logprobs_worst", "loss_gap_policy", "loss_gap_value", "loss_gap_entropy", "update_gap", "update_mid", "moe_dropped"})
# per planted fault, the numbers of which one has to fail
fault_kinds = {
    "unchanged": ("update_gap",),             # an update that leaves its parameters as they were
    "half_steps": ("update_mid",),            # half of each epoch's minibatches skipped
    "half_batch": ("update_mid", "loss_gap"),  # half of each minibatch's sequences left out, the means taken over the rest
}
DATA = ("tokens", "actions", "is_first", "logprobs", "values", "returns", "advantages")  # what the update reads of a rollout


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


def _module():
    from sheeprl_tpu.algos.ppo_recurrent import sequence_policy

    return sequence_policy


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

    stack = contextlib.ExitStack()
    stack.enter_context(run.patched(loop, {"RunGuard": run.wrap_guard}))  # the run around the loop sets the guard up
    stack.enter_context(run.patched(_module(), {"build_agent": partial(seed_weights, run), "make_update_fn": wrap_make_update_fn}))
    return stack


# -- shapes, sizes, work -------------------------------------------------------------
def _spaces(mix: Dict[str, Any]):
    import gymnasium as gym

    (key, spec), = mix["observation"].items()
    n = int(mix["action"]["n"])
    return gym.spaces.Dict({key: gym.spaces.Box(0, n - 1, tuple(spec["shape"]), np.dtype(spec["dtype"]))}), gym.spaces.Discrete(n)


def _composed(spec: Dict[str, Any], rehearse: bool = False):
    from sheeprl_tpu.config import compose

    from ..run import overrides_for

    return compose("config", overrides_for(spec, 0, rehearse))


def sizes_for(cfg: Any, minibatches: int, minibatch_seqs: int) -> reference.Sizes:
    """What the reference needs of a cell, from the composed config the program runs with."""
    a, b = cfg.algo, cfg.algo.backbone
    if str(a.loss_reduction) != "mean" or bool(a.anneal_lr) or float(a.optimizer.get("weight_decay", 0)) != 0:
        raise ValueError("the reference takes means, a fixed learning rate and an Adam without weight decay")
    r = b.rope_scaling
    return reference.Sizes(
        kv_rank=int(b.kv_lora_rank), nope=int(b.qk_nope_head_dim),
        rope=int(b.qk_rope_head_dim), v_dim=int(b.v_head_dim), heads=int(b.heads_held), experts=int(b.n_routed_experts),
        top_k=int(b.num_experts_per_tok), experts_held=int(b.experts_held), first_expert=int(b.first_expert),
        scaling=float(b.routed_scaling_factor), layers=int(b.num_hidden_layers),
        streams=int(b.hc_mult), sinkhorn=int(b.hc_sinkhorn_iters), hc_eps=float(b.hc_eps),
        clamp=(float(b.mhc_h_res_clamp_min), float(b.mhc_h_res_clamp_max)), theta=float(b.rope_theta), factor=float(r.factor),
        beta_fast=float(r.beta_fast), beta_slow=float(r.beta_slow), mscale_all_dim=float(r.mscale_all_dim),
        original_context=int(r.original_max_position_embeddings), norm_eps=float(b.rms_norm_eps), envs=int(cfg.env.num_envs),
        steps=int(a.rollout_steps), gamma=float(a.gamma), gae_lambda=float(a.gae_lambda), epochs=int(a.update_epochs),
        minibatches=int(minibatches), minibatch_seqs=int(minibatch_seqs), normalize_advantages=bool(a.normalize_advantages),
        clip_vloss=bool(a.clip_vloss), lr=float(a.optimizer.lr), eps=float(a.optimizer.eps),
        max_grad_norm=float(a.get("max_grad_norm", 0.0) or 0.0),
    )


WIDTHS = ("hidden_size", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "intermediate_size",
          "moe_intermediate_size", "num_experts_per_tok", "n_shared_experts", "hc_mult", "hc_sinkhorn_iters")


def widths_of(cfg: Any) -> Dict[str, int]:
    """The keys of a configuration file's `widths`, from the composed config the program runs with: every published
    width, the router's width, and the sizes of a rollout and of a minibatch."""
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
    """Tokens of its own episode a token attends to, itself included, on average over a rollout of the mix: an
    episode ends at each inner boundary of `episode_unit` steps with probability `cut_share`."""
    unit, units, keep = int(mix["episode_unit"]), int(mix["rollout_steps"]) // int(mix["episode_unit"]), 1.0 - float(mix["cut_share"])
    whole_units = np.mean([sum(keep ** i for i in range(1, j + 1)) for j in range(units)])  # uncut units behind a token's own
    return (unit + 1) / 2.0 + unit * float(whole_units)


def token_macs(shapes: Dict[str, Any], spec: Dict[str, Any]) -> Dict[str, float]:
    """Multiply-adds one token needs in each part, from the shapes of the program's own build and the routing's
    expected load (`num_experts_per_tok` x held / routed of a token's pairs come to the experts held here): the
    work the token needs, not what an implementation multiplies (no padding, no recompute). `attend_train` and
    `attend_decode` are the attention's products with the context (expanded keys and values; absorbed latents)."""
    w = spec["config"]["widths"]
    held = next(s[0] for n, (s, _) in shapes.items() if n.endswith("moe/experts/w_gate/kernel"))
    share = float(w["num_experts_per_tok"]) * held / float(w["router_width"])
    out = {"mla": 0.0, "moe": 0.0, "experts": 0.0, "dense_mlp": 0.0, "mhc": 0.0, "head": 0.0}
    heads = 0
    for name, (shape, _) in shapes.items():
        if not name.endswith("/kernel"):
            continue
        size = float(np.prod(shape))
        if "/attn/" in name:
            out["mla"] += size
            if name.endswith("layer_0/attn/w_o/kernel"):
                heads = shape[0] // int(w["v_head_dim"])
        elif "_hc/" in name:
            out["mhc"] += size
        elif "/moe/experts/" in name:
            out["experts"] += size / shape[0] * share
        elif "/moe/" in name:
            out["moe"] += size
        elif "/mlp/" in name:
            out["dense_mlp"] += size
        else:
            out["head"] += size
    layers = len({n.split("/")[0] for n in shapes if n.startswith("layer_")})
    ctx = mean_context(spec["mix"])
    qk, v, r = int(w["qk_nope_head_dim"]) + int(w["qk_rope_head_dim"]), int(w["v_head_dim"]), int(w["kv_lora_rank"])
    out["attend_train"] = layers * heads * (qk + v) * ctx
    out["attend_decode"] = layers * heads * ((r + int(w["qk_rope_head_dim"])) + r) * ctx
    return out


def step_flops(shapes: Dict[str, Any], spec: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs of one gradient step under 'total' (a minibatch of whole sequences, forward 2 FLOP a multiply-add and
    backward twice the forward) and of the player's decode step for one env step under 'per_env_step' (one token
    through every kernel it touches and its cache rows). An on-policy loop acts for every token it trains on."""
    w = spec["config"]["widths"]
    macs = token_macs(shapes, spec)
    kernels = sum(v for k, v in macs.items() if not k.startswith("attend_"))
    tokens = int(w["rollout_steps"]) * int(w["minibatch_sequences"])
    # absorbed form: q_nope through W_uk and the latent sum through W_uv are already among the kernels' multiply-adds
    return {"total": 6.0 * (kernels + macs["attend_train"]) * tokens, "per_env_step": 2.0 * (kernels + macs["attend_decode"])}


def expert_flops(shapes: Dict[str, Any], spec: Dict[str, Any], pairs: float) -> float:
    """FLOPs of the grouped expert products, forward and backward, for `pairs` (token, expert) pairs: three kernels a pair."""
    e = next(s for n, (s, _) in shapes.items() if n.endswith("moe/experts/w_gate/kernel"))
    return 6.0 * 3.0 * e[1] * e[2] * pairs


def decode_bytes(shapes: Dict[str, Any], spec: Dict[str, Any]) -> float:
    """Bytes one decode step for all envs must read: every parameter it touches once and the cache rows in use. Of
    the embedding that is the envs' rows; of a held expert's kernels the share of steps in which some env's token
    chooses it: none of `envs` tokens does with probability (1 - k / routed)^envs, 12.7 % at 32 envs and top-4 of 64,
    so about one held expert in eight a layer need not be read in a step (the choices are taken as uniform). This is
    what the work needs, not what the program moves: its acting form of the expert layer reads every held expert
    every step (`models/sequence.py:moe`), which shows as a lower share."""
    envs, w = int(spec["mix"]["num_envs"]), spec["config"]["widths"]
    touched = 1.0 - (1.0 - float(w["num_experts_per_tok"]) / float(w["router_width"])) ** envs
    nbytes = lambda keep: sum(float(np.prod(s)) * np.dtype(d).itemsize for n, (s, d) in shapes.items() if keep(n))  # noqa: E731
    params = nbytes(lambda n: not n.startswith("embed/") and "/moe/experts/" not in n) + touched * nbytes(lambda n: "/moe/experts/" in n)
    params += envs * int(w["hidden_size"]) * 4.0
    layers = len({n.split("/")[0] for n in shapes if n.startswith("layer_")})
    return params + envs * layers * mean_context(spec["mix"]) * (int(w["kv_lora_rank"]) + int(w["qk_rope_head_dim"])) * 4.0


def expert_bytes(shapes: Dict[str, Any]) -> float:
    """Bytes of the held experts' kernels, all expert layers: what the grouped products of one pass read."""
    return sum(float(np.prod(s)) * np.dtype(d).itemsize for n, (s, d) in shapes.items() if "/moe/experts/" in n)


def kept_bytes(shapes: Dict[str, Any], spec: Dict[str, Any]) -> Dict[str, float]:
    """What the cell keeps on the chip across calls: parameters, Adam's two moments and the per-env latent cache."""
    params = float(sum(int(np.prod(s)) * np.dtype(d).itemsize for s, d in shapes.values()))
    w = spec["config"]["widths"]
    layers = len({n.split("/")[0] for n in shapes if n.startswith("layer_")})
    cache = 4.0 * layers * int(spec["mix"]["num_envs"]) * int(w["rollout_steps"]) * (int(w["kv_lora_rank"]) + int(w["qk_rope_head_dim"]))
    return {"params": params, "adam": 2.0 * params, "cache": cache, "total": 3.0 * params + cache}


def rehearse(spec: Dict[str, Any], topology: Any) -> Dict[str, Any]:
    """Compile the decode step and the whole update for a described chip (none attached) at the cell's real sizes:
    what each needs, and the worst case (the update's arguments, which are the kept state, its temp, and the cache)."""
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

    sp = _module()
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
    key = like(jax.eval_shape(lambda: jax.random.key(0)))
    arr = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    out: Dict[str, Any] = {}
    t0 = time.time()
    state = like(jax.eval_shape(lambda: sp.new_state(made["module"], envs, steps)))
    act = sp.make_act_fn(made["module"]).lower(like(params), state, arr(jnp.int32, envs), arr(jnp.bool_, envs), key).compile()
    mem = act.memory_analysis()
    out["act"] = {"args_gb": mem.argument_size_in_bytes / 1e9, "temp_gb": mem.temp_size_in_bytes / 1e9, "compile_s": time.time() - t0}
    t0 = time.time()
    data = {k: arr(jnp.int32 if k in ("tokens", "actions") else jnp.bool_ if k == "is_first" else jnp.float32, envs, steps)
            for k in DATA + ("rewards", "dones")}
    coefs = {k: arr(jnp.float32) for k in ("clip_coef", "ent_coef", "vf_coef", "lr_frac")}
    update = sp.make_update_fn(made["module"], tx, cfg, batches, envs // batches).lower(like(params), like(opt_state), data, coefs, key).compile()
    mem = update.memory_analysis()
    shapes = {k: (x.shape, x.dtype) for k, x in flat_names(params).items()}
    out["update"] = {"args_gb": mem.argument_size_in_bytes / 1e9, "temp_gb": mem.temp_size_in_bytes / 1e9,
                     "code_gb": mem.generated_code_size_in_bytes / 1e9, "compile_s": time.time() - t0}
    out["worst_case_gb"] = (mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes) / 1e9 + kept_bytes(shapes, spec)["cache"] / 1e9
    out["flops_per_grad_step"] = step_flops(shapes, spec)
    return out


# -- the first update against the reference -------------------------------------------------
def rollout_rows(rollout: Dict[str, np.ndarray], envs: Dict[int, Any], sz: reference.Sizes) -> Tuple[int, int]:
    """(rows looked at, rows that differ from what the generator emitted). `rollout` is sequence-major [envs, T];
    step t of env e is the t-th emission of that env that is not a final observation."""
    rows = wrong = 0
    for e in range(sz.envs):
        env = envs.get(e)
        seen = env.seen() if env is not None else []
        for t in range(sz.steps):
            rows += 1
            if t >= len(seen) or seen[t] + 1 >= env.n:
                wrong += 1
                continue
            n = seen[t]
            ok = (
                int(rollout["tokens"][e, t]) == env.log_token[n] and int(rollout["actions"][e, t]) == env.log_action[n]
                # the step that answered emission n emitted n + 1: its reward and whether it closed an episode
                and float(rollout["rewards"][e, t]) == np.float32(env.log_reward[n + 1])
                and float(rollout["dones"][e, t]) == float(env.log_final[n + 1])
                and bool(rollout["is_first"][e, t]) == bool(env.log_first[n] or t == 0)
            )
            wrong += 0 if ok else 1
    return rows, wrong


def rollout_from_generator(spec: Dict[str, Any], seed: int, sz: reference.Sizes) -> Dict[str, np.ndarray]:
    """One rollout [envs, T] from the cell's generator alone, the actions drawn from the seed: the rows a
    calibration needs, of the kind `rollout_rows` accepts."""
    from ..envs import generator_of

    cls, n = generator_of(spec["mix"]), int(spec["mix"]["action"]["n"])
    (key, _), = spec["mix"]["observation"].items()
    out = {k: np.zeros((sz.envs, sz.steps), d) for k, d in (("tokens", np.int32), ("actions", np.int32), ("rewards", np.float32),
                                                             ("dones", np.float32), ("is_first", bool))}
    for e in range(sz.envs):
        env = cls(mix=spec["mix_ref"], seed=0, rank=e, bench_seed=seed)
        actions = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, e, 9]).integers(0, n, sz.steps)
        obs, first = env.reset()[0], True
        for t in range(sz.steps):
            out["tokens"][e, t], out["actions"][e, t], out["is_first"][e, t] = int(obs[key][0]), actions[t], first or t == 0
            obs, reward, term, trunc, _ = env.step(actions[t])
            out["rewards"][e, t], out["dones"][e, t], first = reward, float(term or trunc), bool(term or trunc)
            if first:
                obs = env.reset()[0]
    return out


def delta_norms(after: Dict[str, Any], before: Dict[str, Any]) -> Dict[str, float]:
    import jax.numpy as jnp

    return {k: float(jnp.sqrt(jnp.sum(jnp.square(jnp.asarray(after[k]) - before[k])))) for k in before}


# the planted faults as the reference takes them: (coefs, sizes) of the broken update
REFERENCE_FAULTS = {
    "unchanged": lambda coefs, sz: ({**coefs, "lr_frac": 0.0}, sz),
    "half_steps": lambda coefs, sz: (coefs, sz._replace(minibatches=sz.minibatches // 2)),
    "half_batch": lambda coefs, sz: (coefs, sz._replace(minibatch_seqs=sz.minibatch_seqs // 2)),
}


def reference_side(seed: int, shapes: Dict[str, Any], rollout: Dict[str, np.ndarray], coefs: Dict[str, float], key_data: np.ndarray,
                   sz: reference.Sizes, od: Any = None, stored: Optional[Dict[str, np.ndarray]] = None, fault: str = "") -> Dict[str, Any]:
    """What a run is compared by, from the reference at operand type `od` on the seeded weights: the log-probabilities
    and values of the rollout, the expert choices, the advantages, and the first update's losses and change by leaf.
    The update is fed `stored` (the rollout as a program kept it) where given, else this side's own numbers, and is
    broken as `REFERENCE_FAULTS[fault]` says where a fault is named."""
    import jax.numpy as jnp

    weights = make_weights(seed, shapes)
    tokens, is_first, actions = (jnp.asarray(rollout[k]) for k in ("tokens", "is_first", "actions"))
    logprobs, values, chosen = reference.rollout_forward(nest(dict(weights)), tokens, is_first, actions, sz, od)
    adv = reference.gae(jnp.asarray(rollout["rewards"]).T, values.T, jnp.asarray(rollout["dones"]).T, sz).T
    side = {"logprobs": np.asarray(logprobs), "values": np.asarray(values), "advantages": np.asarray(adv), "chosen": np.asarray(chosen)}
    data = dict(stored) if stored is not None else {**rollout, "logprobs": side["logprobs"], "values": side["values"],
                                                    "advantages": side["advantages"], "returns": side["advantages"] + side["values"]}
    if fault:
        coefs, sz = REFERENCE_FAULTS[fault](coefs, sz)
    after, steps = reference.first_update(weights, {k: data[k] for k in DATA}, coefs, key_data, sz, od)
    side["losses"] = steps[0]
    side["delta"] = delta_norms(after, make_weights(seed, shapes))
    return side


def program_choices(seed: int, shapes: Dict[str, Any], rollout: Dict[str, np.ndarray], cfg: Any, block: int = 4) -> np.ndarray:
    """The expert choices [expert layers, envs, T, k] of the program's own full forward at its own precision over the
    rollout, on the seeded weights, a block of sequences at a time."""
    import jax

    from sheeprl_tpu.models import sequence as seq

    scfg = seq.SequenceConfig.from_node(cfg.algo.backbone)
    params = nest(dict(make_weights(seed, shapes)))
    fwd = jax.jit(lambda p, t, f: seq.forward_train(p, t, f, scfg, remat=False, choices=True)[2]["chosen"])
    parts = []
    for i in range(0, rollout["tokens"].shape[0], block):
        t = rollout["tokens"][i: i + block]
        parts.append(np.asarray(fwd(params, t, rollout["is_first"][i: i + block])).reshape(-1, *t.shape, scfg.num_experts_per_tok))
    return np.concatenate(parts, 1)


def compare_sides(side: Dict[str, Any], ref: Dict[str, Any]) -> Tuple[Dict[str, float], Dict[str, Any]]:
    """A side (the program's numbers, or the reference's at another operand type) against the reference."""
    values: Dict[str, float] = {}
    flipped = np.any(side["chosen"] != ref["chosen"], -1)  # [expert layers, envs, T]
    values["routing_flips"] = float(np.mean(flipped)) if flipped.size else 0.0
    agreed = ~np.any(flipped, 0) if flipped.size else np.ones(ref["values"].shape, bool)
    for name in ("values", "logprobs"):
        gap = np.abs(side[name] - ref[name]) / (float(np.median(np.abs(ref[name]))) + 1e-6)
        values[f"{name}_gap"] = float(np.quantile(gap[agreed], 0.95)) if agreed.any() else float("inf")
        values[f"{name}_worst"] = float(np.max(gap))
    gap = np.abs(side["advantages"] - ref["advantages"]) / (float(np.median(np.abs(ref["advantages"]))) + 1e-6)
    values["advantages_gap"] = float(np.quantile(gap, 0.95))
    for name, want in ref["losses"].items():
        values["loss_gap_" + name.split("/")[1].replace("_loss", "")] = abs(side["losses"][name] - want) / (abs(want) + 0.1)
    values["update_gap"], values["update_mid"], worst = gap_by_leaf(side["delta"], ref["delta"])
    return values, {"worst_leaf": worst, "agreed_tokens": float(np.mean(agreed)),
                    "losses_side_reference": {k: [side["losses"][k], v] for k, v in ref["losses"].items()}}


def decide(run: Run, envs: Dict[int, Any], spec: Dict[str, Any]) -> Tuple[Dict[str, float], Dict[str, float], Dict[str, Any]]:
    """(every number read, the limits the cell's structure gives, the detail)."""
    sz = sizes_for(run.cfg, run.notes["minibatches"], run.notes["minibatch_seqs"])
    values: Dict[str, float] = {}
    detail: Dict[str, Any] = {}

    rows, wrong = rollout_rows(run.rollout, envs, sz)
    detail["rollout"] = {"rows": rows, "wrong_rows": wrong}
    values["rollout_wrong_rows"] = float(wrong)
    values["moe_dropped"] = float(sum(int(np.sum(np.asarray(x))) for x in run.loads))

    # an update is owed every steps x envs env steps: never before they were taken, and the loop is serial
    per_update = sz.steps * sz.envs
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
        side["chosen"] = program_choices(run.seed, run.shapes, run.rollout, run.cfg)
        ref = reference_side(run.seed, run.shapes, run.rollout, run.coefs, run.update_key, sz, stored=run.rollout)
        compared, more = compare_sides(side, ref)
        values.update(compared)
        detail.update(more)
        detail["reference_s"] = time.perf_counter() - t0
    return values, structural, detail


# -- the readings the limits are set from (perfbench/calibrate.py) -------------------------------
def calibrate(spec: Dict[str, Any], seeds: List[int], only=None, rehearse: bool = False):
    """Per seed, the control and the planted fault (the module's docstring lists the sides), each put in the program's
    place and compared with the plain reference by the same numbers as a run, at the cell's own sizes, on a rollout
    from the cell's own generator."""
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
    to nothing, so that it returns its parameters as it got them (no copy of 8 GB of state beside the update).
    `half_steps`: each epoch takes half of its minibatches. `half_batch`: every minibatch holds half of its
    sequences, and the means are taken over those."""
    if kind not in fault_kinds:
        raise ValueError(f"unknown fault {kind!r}")
    sp = _module()
    orig = sp.make_update_fn

    def make_update_fn(module, tx, cfg, num_minibatches, mb_size):
        if kind == "half_steps":
            return orig(module, tx, cfg, num_minibatches // 2, mb_size)
        if kind == "half_batch":
            return orig(module, tx, cfg, num_minibatches, mb_size // 2)
        update = orig(module, tx, cfg, num_minibatches, mb_size)

        def unchanged(params, opt_state, data, coefs, key):
            return update(params, opt_state, data, {**coefs, "lr_frac": coefs["lr_frac"] * 0.0}, key)

        return unchanged

    sp.make_update_fn = make_update_fn
    try:
        yield
    finally:
        sp.make_update_fn = orig
