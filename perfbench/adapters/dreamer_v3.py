"""The DreamerV3 family behind the harness's one seam (PERF.md section 4).

Everything the benchmark knows of `algos/dreamer_v3/`: the taps on its four
names, the shapes of its weight tree, the comparison with its plain reference
(`perfbench/reference.py`), its step's FLOPs and kept bytes (`work.py`), the
rehearsal for a described chip, the readings a limit is set from and the
faults the tests plant. `run.py`, `check.py`, `taps.py`, `rehearse.py`,
`calibrate.py` and the metric readers reach all of it by the name in the
configuration's file (`"adapter": "dreamer_v3"`) and by no import of their own.

Four names in `algos/dreamer_v3/dreamer_v3.py` are wrapped for the length of
one run, none of them replaced:

* ``build_agent``: the weights it returns are overwritten, leaf by leaf and in
  place of the same shape, type and placement, by the benchmark's own seeded
  weights (`reference.make_weights`): the reference then never takes a weight
  the program made;
* ``make_train_fn``: the returned ``train`` is called as the loop calls it and
  every return goes to `taps.Run.stamp`; during set-up the wrapper keeps host
  copies of what the first calls were given and gave back;
* ``RunGuard``: see `taps.Run.wrap_guard`;
* ``make_sequential_prefetcher``: only looked at, to report which ring the
  ``auto`` option resolved to.

What `decide` compares, all of it about what the timed path itself produced at
the timed sizes (the program's own loop, its compiled `train`, its ring):

1. every row of the batches the ring gathered for the first gradient steps,
   against the generator's own log of what it emitted (exact);
2. gradient steps taken against env steps taken, at every train call of the
   run, against what `replay_ratio` owes (structural limits);
3. the first three gradient steps against the plain float32 reference, fed the
   same seeded weights, the same rows and the same PRNG keys: each step's
   losses, the norm of the first gradient as the optimizer gets it (from
   Adam's first moment after one step), and the norm of the parameters' change
   after three steps, by the worst leaf and by the median leaf.
"""
from __future__ import annotations

import contextlib
import time
from functools import partial
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

from .. import reference, work
from ..check import QUEUE_SLACK_PACKETS, gap_by_leaf, leaf_norms, ratio_numbers, replay_rows
from ..taps import Run, flat_names

CHECK_STEPS = 3  # the reference follows the first three gradient steps
GROUPS = ("wm", "actor", "critic")
# every name `decide` may return: a limits file names none but these
compared_numbers = frozenset(
    {f"{kind}_{g}" for g in GROUPS for kind in ("loss1_gap", "loss_gap", "grad_gap", "grad_mid", "update_gap", "update_mid")}
    | {"replay_wrong_rows", "ratio_early_steps", "ratio_late_steps"})
# the faults `faults(kind)` plants, each with the prefixes of the numbers of which one has to fail
fault_kinds = {
    "unchanged": ("update_",),                 # a step that returns its state unchanged
    "unchanged_actor": ("update_gap_actor",),  # the same of the actor alone: no other group's number sees it
    "half_batch": ("grad_", "loss"),           # half of the batch left out, the mean taken over the rest
    "altered_batch": ("replay_wrong_rows",),   # a gathered row altered where it is produced
}
step_programs = ("jit_train",)  # the device programs that are the train step
# the `jax.named_scope` names of `make_train_fn`'s `one_step`, outermost level: the parts `span_reduce` books the step's ops to
step_parts = ("wm_encoder", "wm_rssm", "wm_decoder", "wm_heads", "imagination", "actor", "critic", "optimizer")
# the CPU rehearsal only: the same program at widths a CPU compiles in seconds
rehearsal_overrides = [
    "algo.per_rank_batch_size=4",
    "algo.per_rank_sequence_length=8",
    "algo.horizon=3",
    "algo.dense_units=16",
    "algo.mlp_layers=2",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=8",
    "algo.world_model.transition_model.hidden_size=16",
    "algo.world_model.representation_model.hidden_size=16",
    "algo.world_model.discrete_size=4",
    "algo.world_model.stochastic_size=4",
    "buffer.size=4096",
    "buffer.device_cache=true",
    "algo.learning_starts=128",
]
RESERVED = 0.26e9


# -- the taps ------------------------------------------------------------------------
def seed_weights(run: Run, orig: Callable) -> Callable:
    """`build_agent`, wrapped: the tree's names and shapes go to `run.shapes`
    and the weights it returns are the benchmark's, made from the seed."""

    def build_agent(*args: Any, **kwargs: Any):
        import jax

        # the tree's names and shapes, without running the program's own
        # initializers; the seeded weights then go in through the
        # program's own `state` argument (its resume path)
        dist, cfg, obs_space, actions_dim, is_continuous, key = args[:6]
        run.cfg = cfg
        made: Dict[str, Any] = {}

        def abstract(k):
            made["out"] = orig(dist, cfg, obs_space, actions_dim, is_continuous, k)
            return made["out"][3]

        params = run.seeded(jax.eval_shape(abstract, key), dist.local_device)
        return orig(dist, cfg, obs_space, actions_dim, is_continuous, key, params)

    return build_agent


def _timed(run: Run, train: Callable) -> Callable:
    def timed_train(params, opt_states, moments, batches, keys):
        import jax

        n = len(run.calls_t)
        g = int(keys.shape[0])
        checking = n < CHECK_STEPS
        if checking:
            t0 = time.perf_counter()
            if g != 1:
                raise RuntimeError(f"the first train calls must take one gradient step each, got G={g}")
            run.batches.append({k: np.asarray(v)[0] for k, v in batches.items()})
            run.keys.append(np.asarray(jax.random.key_data(keys))[0])
            run.check_s += time.perf_counter() - t0
        run.before_call()
        out = train(params, opt_states, moments, batches, keys)
        if checking:
            t0 = time.perf_counter()
            new_params, new_opt, _, metrics = out
            run.losses.append({
                "wm": float(np.asarray(metrics["Loss/world_model_loss"])[0]),
                "actor": float(np.asarray(metrics["Loss/policy_loss"])[0]),
                "critic": float(np.asarray(metrics["Loss/value_loss"])[0]),
            })
            if n == 0:
                run.mu1 = {k: np.asarray(v) for k, v in flat_names(new_opt).items() if "/mu/" in k}
            if n == CHECK_STEPS - 1:
                run.params_after = {k: np.asarray(v) for k, v in flat_names(new_params).items()}
            run.check_s += time.perf_counter() - t0
        run.stamp(g, out[0])
        return out

    return timed_train


def _watch_prefetcher(run: Run, orig: Callable) -> Callable:
    def make_sequential_prefetcher(*args: Any, **kwargs: Any):
        prefetcher = orig(*args, **kwargs)
        run.notes["ring"] = type(prefetcher).__name__
        return prefetcher

    return make_sequential_prefetcher


def installed(run: Run):
    """The context manager that hangs the taps on the program for one run."""
    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3

    run.warmup_calls = max(run.warmup_calls, CHECK_STEPS + 1)
    # host copies for the comparison: per check step the batch and key the
    # call got and the losses it returned; mu after step 1; the parameters
    # after step CHECK_STEPS
    run.batches, run.keys, run.losses = [], [], []
    run.mu1 = run.params_after = None
    return run.patched(dv3, {
        "build_agent": partial(seed_weights, run),
        "make_train_fn": lambda orig: lambda *args, **kwargs: _timed(run, orig(*args, **kwargs)),
        "RunGuard": run.wrap_guard,
        "make_sequential_prefetcher": partial(_watch_prefetcher, run),
    })


# -- shapes, sizes, work -------------------------------------------------------------
def sizes_for(cfg: Any, mix: Dict[str, Any]) -> reference.Sizes:
    """What the reference needs of a cell: every size and every hyperparameter
    of the losses and the optimizers from the composed config the program runs
    with, the action space from the mix."""
    a = cfg.algo
    wm, actor, critic = a.world_model, a.actor, a.critic
    if float(a.layer_norm_eps) != reference.LN_EPS or not bool(a.hafner_initialization) or bool(wm.decoupled_rssm):
        raise ValueError("the reference has no such path: layer_norm_eps, hafner_initialization or decoupled_rssm differ")
    if float(wm.kl_regularizer) != 1.0 or float(wm.continue_scale_factor) != 1.0:
        raise ValueError("the reference weighs the KL term and the continue loss by 1")
    if mix["action"]["type"] != "discrete":
        raise ValueError("the reference imagines discrete actions only (see PERF.md section 4)")
    for opt in (wm.optimizer, actor.optimizer, critic.optimizer):
        if float(opt.get("weight_decay", 0)) != 0:
            raise ValueError("the reference's Adam has no weight decay")
    return reference.Sizes(
        stoch=int(wm.stochastic_size), discrete=int(wm.discrete_size),
        recurrent=int(wm.recurrent_model.recurrent_state_size), horizon=int(a.horizon),
        actions=int(mix["action"]["n"]), unimix=float(a.unimix), gamma=float(a.gamma), lmbda=float(a.lmbda),
        ent_coef=float(actor.ent_coef), kl_dynamic=float(wm.kl_dynamic), kl_representation=float(wm.kl_representation),
        kl_free_nats=float(wm.kl_free_nats), tau=float(critic.tau),
        moments_decay=float(actor.moments.decay), moments_max=float(actor.moments.max),
        moments_low=float(actor.moments.percentile.low), moments_high=float(actor.moments.percentile.high),
        wm_lr=float(wm.optimizer.lr), wm_eps=float(wm.optimizer.eps), wm_clip=float(wm.clip_gradients),
        actor_lr=float(actor.optimizer.lr), actor_eps=float(actor.optimizer.eps), actor_clip=float(actor.clip_gradients),
        critic_lr=float(critic.optimizer.lr), critic_eps=float(critic.optimizer.eps), critic_clip=float(critic.clip_gradients),
        image_keys=tuple(a.cnn_keys.encoder), vector_keys=tuple(a.mlp_keys.encoder),
        vector_decoder_keys=tuple(a.mlp_keys.decoder),
    )


def widths_of(cfg: Any) -> Dict[str, int]:
    """The keys of a configuration file's `widths`, from the composed config the program runs with."""
    a = cfg.algo
    wm = a.world_model
    return {
        "dense_units": int(a.dense_units), "mlp_layers": int(a.mlp_layers),
        "recurrent_state_size": int(wm.recurrent_model.recurrent_state_size),
        "cnn_channels_multiplier": int(wm.encoder.cnn_channels_multiplier),
        "stochastic_size": int(wm.stochastic_size), "discrete_size": int(wm.discrete_size),
        "transition_hidden_size": int(wm.transition_model.hidden_size),
        "representation_hidden_size": int(wm.representation_model.hidden_size),
        "horizon": int(a.horizon), "per_rank_sequence_length": int(a.per_rank_sequence_length),
        "per_rank_batch_size": int(a.per_rank_batch_size),
        "reward_bins": int(wm.reward_model.bins), "critic_bins": int(a.critic.bins),
    }


def _spaces(mix: Dict[str, Any]):
    import gymnasium as gym

    space = {k: gym.spaces.Box(0, 255, tuple(v["shape"]), np.dtype(v["dtype"])) for k, v in mix["observation"].items()}
    if mix.get("reward_as_observation"):
        space["reward"] = gym.spaces.Box(-np.inf, np.inf, (1,), np.float32)
    return gym.spaces.Dict(space)


def program_shapes(spec: Dict[str, Any], rehearse: bool = False) -> Tuple[Any, Dict[str, Tuple[Tuple[int, ...], Any]]]:
    """(composed config, {leaf name: (shape, dtype)}) of a cell, by
    `jax.eval_shape` over the program's own `build_agent`: nothing is
    initialised. A run reads the same through `seed_weights`; this is for what
    runs no program (calibrate.py, rehearse.py, the tests)."""
    import jax

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.parallel import Distributed

    from ..run import overrides_for

    mix = spec["mix"]
    cfg = compose("config", overrides_for(spec, 0, rehearse))
    dist = Distributed(devices=1)
    actions = [int(mix["action"]["n"])]
    tree = jax.eval_shape(lambda k: build_agent(dist, cfg, _spaces(mix), actions, False, k)[3], jax.random.key(0))
    return cfg, {n: (tuple(x.shape), np.dtype(x.dtype)) for n, x in flat_names(tree).items()}


def step_flops(shapes: Dict[str, Any], spec: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs of one gradient step at the configuration's stated [T, B] and horizon, by part and under 'total';
    under 'per_env_step' the player's forward for one env step of one env (0.014 % of the window's FLOPs at
    `dv3_xl.crafter`, 0.5 % at `dv3_l.navigate4`, 64 env steps a gradient step)."""
    w = spec["config"]["widths"]
    out = work.train_step_flops(shapes, int(w["per_rank_sequence_length"]), int(w["per_rank_batch_size"]), int(w["horizon"]))
    out["per_env_step"] = work.act_flops(shapes)
    return out


def kept_bytes(shapes: Dict[str, Any], spec: Dict[str, Any]) -> Dict[str, float]:
    """What the cell keeps on the chip across calls: parameters, Adam's moments and the ring."""
    mix = spec["mix"]
    return work.kept_bytes(shapes, mix, int(spec["config"]["buffer.size"]), int(mix["action"]["n"]))


def rehearse(spec: Dict[str, Any], topology: Any) -> Dict[str, Any]:
    """Compile `_gather_batch`, `_scatter_rows` and `train` for a described
    chip (none attached) at the cell's real sizes: what each needs, and the
    worst case (state + train temp + 3 x ring + what the runtime reserves). Nothing runs, so nothing here is a
    time or a result."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import build_optimizers, make_train_fn
    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.data.device_ring import _gather_batch, _scatter_rows
    from sheeprl_tpu.parallel import Distributed

    from ..run import overrides_for

    one_chip = SingleDeviceSharding(topology.devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def like(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    def items_sds(lead, items):
        return {k: sds(tuple(lead) + shape, jnp.uint8 if np.dtype(d) == np.uint8 else jnp.float32) for k, (shape, d) in items.items()}

    mix = spec["mix"]
    cfg = compose("config", overrides_for(spec, 0, False) + ["algo.world_model.conv_impl=xla"])
    T, B = int(cfg.algo.per_rank_sequence_length), int(cfg.algo.per_rank_batch_size)
    A = int(mix["action"]["n"])
    dist = Distributed(devices=1)
    made = {}

    def build(key):
        wm, actor, critic, params = build_agent(dist, cfg, _spaces(mix), [A], False, key)
        made["mods"] = (wm, actor, critic)
        return params

    params = jax.eval_shape(build, jax.random.key(0))
    wm, actor, critic = made["mods"]
    made_tx = {}

    def opt(p):
        txs, states = build_optimizers(cfg, p)
        made_tx["txs"] = txs
        return states

    opt_states = jax.eval_shape(opt, params)
    train = make_train_fn(wm, actor, critic, made_tx["txs"], cfg, False, [A])
    items = work.ring_items(mix, A)
    batch = items_sds((1, T, B), items)
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(1), 1))
    out: Dict[str, Any] = {}
    t0 = time.time()
    compiled = train.lower(like(params), like(opt_states), like(init_moments()), batch, like(keys)).compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    out["xla_cost_analysis_flops"] = float(cost.get("flops", float("nan")))
    out["train"] = {"args_gb": mem.argument_size_in_bytes / 1e9, "temp_gb": mem.temp_size_in_bytes / 1e9,
                    "code_gb": mem.generated_code_size_in_bytes / 1e9, "compile_s": time.time() - t0}
    rows, n_envs = int(cfg.buffer.size), int(cfg.env.num_envs)
    ring = items_sds((rows, n_envs), items)
    mem = _gather_batch.lower(ring, sds((1, T, B), jnp.int32), sds((B,), jnp.int32), ()).compile().memory_analysis()
    out["gather"] = {"args_gb": mem.argument_size_in_bytes / 1e9, "temp_gb": mem.temp_size_in_bytes / 1e9}
    n = 8 if n_envs == 1 else 72
    mem = _scatter_rows.lower(ring, items_sds((n,), items), sds((n,), jnp.int32), sds((n,), jnp.int32)).compile().memory_analysis()
    out["scatter"] = {"args_gb": mem.argument_size_in_bytes / 1e9, "temp_gb": mem.temp_size_in_bytes / 1e9,
                      "alias_gb": mem.alias_size_in_bytes / 1e9}
    shapes = {k: (x.shape, x.dtype) for k, x in flat_names(params).items()}
    kept = kept_bytes(shapes, spec)
    worst = (kept["params"] + kept["adam"]) / 1e9 + out["train"]["temp_gb"] + 3 * kept["ring"] / 1e9 + RESERVED / 1e9
    out["worst_case_gb"] = worst
    out["flops_per_grad_step"] = step_flops(shapes, spec)
    return out


# -- the first gradient steps against the reference -------------------------------------
def mu_to_params_name(name: str) -> str:
    group, _, rest = name.partition("/")
    return f"{group}/{rest.split('/mu/', 1)[1]}"


def reference_side(seed: int, shapes: Dict[str, Any], batches, keys, sz: reference.Sizes, od=None,
                   faults: Tuple[str, ...] = (), params_after: Dict[str, np.ndarray] = None) -> Dict[str, Any]:
    """The reference (or, with `od` or `faults`, the control or a planted
    fault) over the check steps: losses, leaf norms of the first clipped
    gradient, leaf norms of the parameters' change. With `params_after` (the
    program's parameters after the check steps) also the program's change
    against the same seeded weights."""
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    w0 = reference.make_weights(seed, shapes)
    state = reference.init_state(reference.nest(dict(w0)))
    step = jax.jit(partial(reference.step, sz=sz, od=od, faults=faults))

    @jax.jit
    def norms(tree):
        return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree)

    @jax.jit
    def delta_norms(a, b):
        return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b)

    side: Dict[str, Any] = {"losses": []}
    for i in range(CHECK_STEPS):
        batch = {k: jnp.asarray(v) for k, v in batches[i].items()}
        key = jax.random.wrap_key_data(jnp.asarray(keys[i]))
        state, losses, grads = step(state, batch, key)
        side["losses"].append({k: float(v) for k, v in losses.items()})
        if i == 0:
            side["g1"] = {k: float(v) for k, v in reference.flatten(jax.device_get(norms(grads))).items()}
    if "unchanged" in faults:
        state = reference.init_state(reference.nest(dict(w0)))
    if "unchanged_actor" in faults:
        state["params"]["actor"] = reference.nest(dict(w0))["actor"]
    delta = jax.device_get(delta_norms(reference.flatten(state["params"]), dict(w0)))
    side["delta"] = {k: float(v) for k, v in delta.items()}
    if params_after is not None:
        prog = {k: jnp.asarray(v) for k, v in params_after.items()}
        side["program_delta"] = {k: float(v) for k, v in jax.device_get(delta_norms(prog, dict(w0))).items()}
        del prog
    del state, w0
    side["seconds"] = time.perf_counter() - t0
    return side


def compare_sides(prog: Dict[str, Any], ref: Dict[str, Any]) -> Tuple[Dict[str, float], Dict[str, Any]]:
    """The numbers compared: `prog` is the program (or the control, or a
    fault) and `ref` the reference, each as {"losses", "g1", "delta"}."""
    out: Dict[str, float] = {}
    detail: Dict[str, Any] = {"losses_program_reference": [], "worst_leaf": {}}
    for i in range(CHECK_STEPS):
        detail["losses_program_reference"].append({g: [prog["losses"][i][g], ref["losses"][i][g]] for g in GROUPS})
    for g in GROUPS:
        gaps = [abs(prog["losses"][i][g] - ref["losses"][i][g]) / (abs(ref["losses"][i][g]) + 0.1) for i in range(CHECK_STEPS)]
        out[f"loss1_gap_{g}"] = gaps[0]
        out[f"loss_gap_{g}"] = max(gaps)
    left_out: List[str] = []
    for g in GROUPS:
        ref_g = {k: v for k, v in ref["g1"].items() if k.startswith(g + "/")}
        worst, mid, name = gap_by_leaf({k: prog["g1"][k] for k in ref_g}, ref_g)
        out[f"grad_gap_{g}"], out[f"grad_mid_{g}"] = worst, mid
        detail["worst_leaf"][f"grad_gap_{g}"] = name
        # leaves whose gradient is nought to rounding move under Adam by round-off alone
        med = float(np.median(list(ref_g.values())))
        moved = {k for k, v in ref_g.items() if v >= 1e-3 * med}
        left_out += sorted(set(ref_g) - moved)
        worst, mid, name = gap_by_leaf({k: prog["delta"][k] for k in ref_g}, {k: ref["delta"][k] for k in ref_g}, keep=moved.__contains__)
        out[f"update_gap_{g}"], out[f"update_mid_{g}"] = worst, mid
        detail["worst_leaf"][f"update_gap_{g}"] = name
    detail["leaves_left_out_of_update"] = left_out
    return out, detail


def program_side(run, ref: Dict[str, Any]) -> Dict[str, Any]:
    """What the taps kept of the program's first steps, in the reference's terms."""
    g1 = {mu_to_params_name(k): v * 10.0 for k, v in leaf_norms(run.mu1).items()}  # mu1 = (1 - 0.9) g1
    return {"losses": run.losses, "g1": g1, "delta": ref["program_delta"]}


def reference_numbers(run, spec: Dict[str, Any]) -> Tuple[Dict[str, float], Dict[str, Any]]:
    sz = sizes_for(run.cfg, spec["mix"])
    ref = reference_side(run.seed, run.shapes, run.batches, run.keys, sz, params_after=run.params_after)
    out, detail = compare_sides(program_side(run, ref), ref)
    detail["reference_s"] = ref["seconds"]
    return out, detail



def decide(run: Run, envs: Dict[int, Any], spec: Dict[str, Any]) -> Tuple[Dict[str, float], Dict[str, float], Dict[str, Any]]:
    """(every number read, the limits the cell's structure gives, the detail)."""
    mix = dict(spec["mix"])
    mix["_learning_starts"] = int(run.cfg.algo.learning_starts)
    image_key = next(iter(mix["observation"]))
    values: Dict[str, float] = {}
    detail: Dict[str, Any] = {}

    rows, wrong, broken = replay_rows(run.batches, envs, image_key)
    detail["replay"] = {"rows": rows, "wrong_rows": wrong, "broken_sequences": broken}
    values["replay_wrong_rows"] = float(wrong + broken)

    values.update(ratio_numbers(run, envs, mix))
    n_envs = int(mix["num_envs"])
    ratio = float(mix["replay_ratio"])
    structural = {
        "replay_wrong_rows": 0.0,
        "ratio_early_steps": 0.5 / ratio + n_envs,
        "ratio_late_steps": 0.5 / ratio + QUEUE_SLACK_PACKETS * n_envs,
    }

    ref_values, ref_detail = reference_numbers(run, spec)
    values.update(ref_values)
    detail.update(ref_detail)
    return values, structural, detail


# -- the faults the tests plant (never used by a benchmark run) -----------------------------
def _broken_train(kind: str, train: Callable) -> Callable:
    import jax
    import jax.numpy as jnp

    def unchanged(params, opt_states, moments, batches, keys):
        kept = jax.tree.map(jnp.copy, (params, opt_states, moments))
        out = train(params, opt_states, moments, batches, keys)
        return (*kept, out[3])

    def unchanged_actor(params, opt_states, moments, batches, keys):
        kept = jax.tree.map(jnp.copy, params["actor"])
        new_params, *rest = train(params, opt_states, moments, batches, keys)
        return ({**new_params, "actor": kept}, *rest)

    def half_batch(params, opt_states, moments, batches, keys):
        half = {k: v[:, :, : v.shape[2] // 2] for k, v in batches.items()}
        return train(params, opt_states, moments, half, keys)

    return {"unchanged": unchanged, "unchanged_actor": unchanged_actor, "half_batch": half_batch}[kind]


class _AlteredPrefetcher:
    """The ring's answer altered where it is produced: one pixel of one row."""

    def __init__(self, inner: Any):
        self._inner = inner

    def __getattr__(self, name: str) -> Any:
        return getattr(self._inner, name)

    def take(self, g: int) -> Any:
        batch = dict(self._inner.take(g))
        key = next(k for k, v in batch.items() if v.ndim == 6)
        batch[key] = batch[key].at[0, 3, 1, 5, 5, 0].add(1)
        return batch



@contextlib.contextmanager
def faults(kind: str):
    """The timed path broken beneath the harness's own wrappers, so that the
    harness sees only what a broken program would show it."""
    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3

    if kind in ("unchanged", "unchanged_actor", "half_batch"):
        name, orig = "make_train_fn", dv3.make_train_fn
        patched = lambda *a, **k: _broken_train(kind, orig(*a, **k))  # noqa: E731
    elif kind == "altered_batch":
        name, orig = "make_sequential_prefetcher", dv3.make_sequential_prefetcher
        patched = lambda *a, **k: _AlteredPrefetcher(orig(*a, **k))  # noqa: E731
    else:
        raise ValueError(f"unknown fault {kind!r}")
    setattr(dv3, name, patched)
    try:
        yield
    finally:
        setattr(dv3, name, orig)


# -- the readings a limit is set from (calibrate.py) ----------------------------------------
def batches_from_generator(mix_name: str, mix, seed: int, T: int, B: int, steps: int, n_batches: int):
    """[T, B] batches as the loop would store them, from the generator alone."""
    from ..envs import _rng, generator_of

    SyntheticEnv = generator_of(mix)
    n_envs = int(mix["num_envs"])
    A = int(mix["action"]["n"])
    rows = []
    for e in range(n_envs):
        env = SyntheticEnv(mix_name, bench_seed=seed, rank=e)
        rng = _rng(seed, e, 7)
        obs, _ = env.reset()
        out = []

        def row(obs, n, action):
            a = np.zeros((A,), np.float32)
            if action is not None:
                a[action] = 1.0
            final = env.log_final[n]
            return {
                "rgb": obs["rgb"], "reward": np.array([env.log_reward[n]], np.float32), "actions": a,
                "rewards": np.array([env.log_reward[n]], np.float32),
                "terminated": np.array([float(env.log_terminated[n] and final)], np.float32),
                "truncated": np.array([float(env.log_truncated[n] and final)], np.float32),
                "is_first": np.array([float(env.log_first[n])], np.float32),
            }

        for _ in range(steps):
            a = int(rng.integers(0, A))
            n = env.n - 1
            prev_obs = obs
            obs, r, term, trunc, _ = env.step(a)
            out.append(row(prev_obs, n, a))
            if term or trunc:
                out.append(row(obs, env.n - 1, None))
                obs, _ = env.reset()
        rows.append(out)
    rng = _rng(seed, 99)
    batches = []
    for _ in range(n_batches):
        cols = []
        for b in range(B):
            e = int(rng.integers(0, n_envs))
            s = int(rng.integers(0, len(rows[e]) - T))
            cols.append(rows[e][s:s + T])
        batches.append({k: np.stack([np.stack([cols[b][t][k] for b in range(B)]) for t in range(T)]) for k in cols[0][0]})
    return batches



def calibrate(spec: Dict[str, Any], seeds: List[int], only=None, rehearse: bool = False):
    """Per seed, the control (the reference with every matmul and conv operand
    rounded to float8_e4m3fn, the precision below the bfloat16 operands the
    configuration states) and the planted faults (half of the batch left out;
    the state left unchanged), each put in the program's place and compared
    with the plain reference by the same numbers as a run, at the cell's own
    sizes, on rows from the cell's own generator. `only` names the sides to
    read, of control_fp8, fault_half_batch, fault_unchanged,
    fault_unchanged_actor and bf16_operands; all of them without it."""
    import jax
    import jax.numpy as jnp

    cfg, shapes = program_shapes(spec, rehearse)
    sz = sizes_for(cfg, spec["mix"])
    T, B = int(cfg.algo.per_rank_sequence_length), int(cfg.algo.per_rank_batch_size)
    # bf16_operands is no control: it is what a TPU's default precision does to `32-true`, read
    # to show how far that alone moves each number from pure float32
    sides = {
        "control_fp8": {"od": jnp.float8_e4m3fn}, "fault_half_batch": {"faults": ("half_batch",)},
        "fault_unchanged": {"faults": ("unchanged",)}, "fault_unchanged_actor": {"faults": ("unchanged_actor",)},
        "bf16_operands": {"od": jnp.bfloat16},
    }
    sides = {k: v for k, v in sides.items() if only is None or k in only}
    for seed in seeds:
        t0 = time.time()
        batches = batches_from_generator(spec["cell"]["traffic"], spec["mix"], seed, T, B, 200 if rehearse else 1100, CHECK_STEPS)
        keys = [np.asarray(jax.random.key_data(jax.random.split(jax.random.key(seed % 2147483647 + i), 1)))[0] for i in range(CHECK_STEPS)]
        ref = reference_side(seed, shapes, batches, keys, sz)
        rec = {"seed": seed}
        for name, kw in sides.items():
            rec[name], _ = compare_sides(reference_side(seed, shapes, batches, keys, sz, **kw), ref)
        rec["seconds"] = time.time() - t0
        yield rec
