"""The comparison that decides `correct`.

Three things are compared, all of them about what the timed path itself
produced at the timed sizes (the program's own loop, its compiled `train`,
its ring, through `cli.run`):

1. every row of the batches the ring gathered for the first gradient steps,
   against the generator's own log of what it emitted (exact);
2. gradient steps taken against env steps taken, at every train call of the
   run, against what `replay_ratio` owes (structural limits);
3. the first three gradient steps against the plain float32 reference
   (`reference.py`), fed the same seeded weights, the same rows and the same
   PRNG keys: each step's losses, the norm of the first gradient as the
   optimizer gets it (from Adam's first moment after one step), and the norm
   of the parameters' change after three steps, by the worst leaf and by the
   median leaf.

Every number goes out beside its limit. Limits live in
`perfbench/limits/<config>.json`, with the readings they were set from in
PERF.md; a number without a limit there is printed in the detail only.
"""
from __future__ import annotations

import json
import os
import time
from functools import partial
from typing import Any, Dict, List, Tuple

import numpy as np

from . import reference
from .taps import CHECK_STEPS

HERE = os.path.dirname(os.path.abspath(__file__))
GROUPS = ("wm", "actor", "critic")
QUEUE_SLACK_PACKETS = 16  # player runs at most queue_depth (4) + in-flight packets ahead


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


def program_shapes(spec: Dict[str, Any], rehearse: bool = False) -> Tuple[Any, Dict[str, Tuple[Tuple[int, ...], Any]]]:
    """(composed config, {leaf name: (shape, dtype)}) of a cell, by
    `jax.eval_shape` over the program's own `build_agent`: nothing is
    initialised. A run reads the same through its wrapper (taps.py); this is
    for what runs no program (calibrate.py, rehearse.py, the tests)."""
    import gymnasium as gym
    import jax

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.parallel import Distributed

    from .run import overrides_for
    from .taps import flat_names

    mix = spec["mix"]
    cfg = compose("config", overrides_for(spec, 0, rehearse))
    space = {k: gym.spaces.Box(0, 255, tuple(v["shape"]), np.dtype(v["dtype"])) for k, v in mix["observation"].items()}
    if mix.get("reward_as_observation"):
        space["reward"] = gym.spaces.Box(-np.inf, np.inf, (1,), np.float32)
    dist = Distributed(devices=1)
    actions = [int(mix["action"]["n"])]
    tree = jax.eval_shape(lambda k: build_agent(dist, cfg, gym.spaces.Dict(space), actions, False, k)[3], jax.random.key(0))
    return cfg, {n: (tuple(x.shape), np.dtype(x.dtype)) for n, x in flat_names(tree).items()}


# -- 1. the gathered rows ----------------------------------------------------------
def replay_rows(batches: List[Dict[str, np.ndarray]], envs: Dict[int, Any], image_key: str) -> Tuple[int, int, int]:
    """(rows looked at, rows that differ from what the generator emitted,
    columns whose rows are not consecutive emissions of one env)."""
    from .envs import SyntheticEnv

    rows = wrong = broken = 0
    for batch in batches:
        T, B = batch["rewards"].shape[:2]
        for b in range(B):
            prev = None
            for t in range(T):
                rows += 1
                img = batch[image_key][t, b]
                e, n = SyntheticEnv.decode(img)
                env = envs.get(e)
                if env is None or n >= env.n:
                    wrong += 1
                    prev = None
                    continue
                if prev is not None and (e, n) != (prev[0], prev[1] + 1):
                    broken += 1
                prev = (e, n)
                action = env.log_action[n]
                want_action = np.zeros_like(batch["actions"][t, b])
                if action is not None and not env.log_final[n]:
                    if want_action.shape[-1] == np.asarray(action).size and np.asarray(action).dtype.kind == "f":
                        want_action = np.asarray(action, np.float32).reshape(-1)
                    else:
                        want_action[int(np.asarray(action).reshape(-1)[0])] = 1.0
                final = env.log_final[n]
                ok = (
                    np.array_equal(img, env.frame(image_key, n))
                    and float(batch["rewards"][t, b, 0]) == np.float32(env.log_reward[n])
                    and ("reward" not in batch or float(batch["reward"][t, b, 0]) == np.float32(env.log_reward[n]))
                    and float(batch["terminated"][t, b, 0]) == float(env.log_terminated[n] and final)
                    and float(batch["truncated"][t, b, 0]) == float(env.log_truncated[n] and final)
                    and float(batch["is_first"][t, b, 0]) == float(env.log_first[n])
                    # the player stores its straight-through sample, one-hot to a float32 rounding
                    and (n == env.n - 1 or np.allclose(batch["actions"][t, b], want_action, rtol=0.0, atol=1e-6))
                )
                wrong += 0 if ok else 1
    return rows, wrong, broken


# -- 2. the ratio -------------------------------------------------------------------
def ratio_numbers(run, envs: Dict[int, Any], mix: Dict[str, Any]) -> Dict[str, float]:
    ratio = float(mix["replay_ratio"])
    s0 = float(mix["_learning_starts"])
    exits = np.sort(np.concatenate([np.asarray(e.t_exit) for e in envs.values()]))
    calls_t = np.asarray(run.calls_t)
    k = np.cumsum(np.asarray(run.calls_g))
    n_env = np.searchsorted(exits, calls_t, side="right")
    enters = np.sort(np.concatenate([np.asarray(e.t_enter) for e in envs.values()]))
    n_started = np.searchsorted(enters, calls_t, side="right")
    due = s0 + k / ratio
    return {
        "ratio_early_steps": float(np.max(due - n_started)),
        "ratio_late_steps": float(np.max(n_env - due)),
    }


# -- 3. the first gradient steps against the reference ----------------------------------
def leaf_norms(tree_flat: Dict[str, Any]) -> Dict[str, float]:
    return {k: float(np.sqrt(np.sum(np.square(np.asarray(v, np.float64))))) for k, v in tree_flat.items()}


def gap_by_leaf(prog: Dict[str, float], ref: Dict[str, float], keep=None) -> Tuple[float, float, str]:
    """Worst and median over leaves of |prog - ref| / max(ref, median ref)."""
    names = [n for n in ref if keep is None or keep(n)]
    mid = float(np.median([ref[n] for n in names])) if names else 0.0
    gaps = {n: abs(prog[n] - ref[n]) / max(ref[n], mid, 1e-30) for n in names}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], float(np.median(list(gaps.values()))), worst


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


def load_limits(config_name: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "limits", f"{config_name}.json")) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def decide(run, envs: Dict[int, Any], spec: Dict[str, Any]) -> Tuple[Dict[str, Dict[str, Any]], Dict[str, Any]]:
    mix = dict(spec["mix"])
    mix["_learning_starts"] = int(run.cfg.algo.learning_starts)
    limits = load_limits(spec["config"]["name"])
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

    compared: Dict[str, Dict[str, Any]] = {}
    for name, value in values.items():
        limit = structural.get(name, limits.get(name))
        if limit is None:
            detail.setdefault("not_compared", {})[name] = value
            continue
        compared[name] = {"value": value, "limit": limit, "ok": bool(np.isfinite(value) and value <= limit)}
    return compared, detail
