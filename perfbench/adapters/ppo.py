"""PPO (the coupled on-policy loop, `algos/ppo/ppo.py`) behind the harness's
seam: the proof that the seam takes a second algorithm as files alone. It is
run by the tests on the CPU (tests/perfbench/fixtures/ppo_bench.json) and is
in no benchmark: an MLP and KBs of state reach no floor of a cell's size.

Three names of `algos/ppo/ppo.py` are wrapped for the length of one run:

* ``build_agent``: the parameters it returns are the benchmark's own seeded
  weights, through its own `params` argument (the resume path);
* ``make_update_fn``: the returned ``update`` (all epochs and minibatches of
  one rollout, one jitted program) is the train call; G = update epochs x
  minibatches. During set-up the wrapper keeps host copies of what the first
  call was given (the whole rollout, the coefficients, the key) and gave back;
* ``RunGuard``: see `taps.Run.wrap_guard`.

What `decide` compares: every row of the first rollout against the
generator's own log (exact); env steps taken against what the updates owe
(structural); and the first update against the plain float32 reference
(`perfbench/references/ppo.py`) on the same seeded weights and rollout: the
values and log-probabilities the player stored, the advantages, the three
losses and the parameters' change by the worst leaf.
"""
from __future__ import annotations

import contextlib
import time
from functools import partial
from typing import Any, Callable, Dict, Tuple

import numpy as np

from ..check import gap_by_leaf
from ..reference import make_weights
from ..references import ppo as reference
from ..taps import Run, flat_names

CHECK_CALLS = 1  # the reference follows the first update: the only rollout acted with the seeded weights
step_programs = ("jit_update",)  # the device program that is the train step
step_parts = ()  # `make_update_fn` has no `jax.named_scope`: nothing to book by part, so the per-part readers read nothing
# the CPU rehearsal only: widths a CPU compiles in seconds
rehearsal_overrides = ["algo.dense_units=16", "algo.mlp_layers=2", "algo.encoder.mlp_features_dim=16"]
# every name `decide` may return: a limits file names none but these
compared_numbers = frozenset({
    "rollout_wrong_rows", "update_early_steps", "update_late_steps", "values_gap", "logprobs_gap", "advantages_gap",
    "loss_gap_policy", "loss_gap_value", "loss_gap_entropy", "update_gap", "update_mid"})
# the fault `faults(kind)` plants, with the prefixes of the numbers of which one has to fail
fault_kinds = {"unchanged": ("update_gap",)}  # an update that returns its parameters unchanged


# -- the taps ------------------------------------------------------------------------
def seed_weights(run: Run, orig: Callable) -> Callable:
    """`build_agent`, wrapped: the tree's names and shapes go to `run.shapes`
    and the parameters it returns are the benchmark's, made from the seed."""

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
            run.losses = {k: float(np.asarray(v)) for k, v in out[2].items()}
            run.check_s += time.perf_counter() - t0
        run.stamp(g, out[0])
        return out

    return timed_update


def installed(run: Run):
    """The context manager that hangs the taps on the program for one run."""
    from sheeprl_tpu.algos.ppo import ppo

    run.warmup_calls = max(run.warmup_calls, CHECK_CALLS + 1)
    run.rollout = run.coefs = run.update_key = run.params_after = run.losses = None

    def wrap_make_update_fn(orig: Callable) -> Callable:
        def make_update_fn(module, tx, cfg, num_minibatches, mb_size):
            run.notes["minibatches"], run.notes["minibatch_rows"] = int(num_minibatches), int(mb_size)
            return _timed(run, orig(module, tx, cfg, num_minibatches, mb_size), int(cfg.algo.update_epochs) * int(num_minibatches))

        return make_update_fn

    return run.patched(ppo, {
        "build_agent": partial(seed_weights, run),
        "make_update_fn": wrap_make_update_fn,
        "RunGuard": run.wrap_guard,
    })


# -- shapes, sizes, work -------------------------------------------------------------
def _spaces(mix: Dict[str, Any]):
    import gymnasium as gym

    obs = {k: gym.spaces.Box(-np.inf, np.inf, tuple(v["shape"]), np.dtype(v["dtype"])) for k, v in mix["observation"].items()}
    if mix["action"]["type"] != "discrete":
        raise ValueError("the reference takes one discrete action a step")
    return gym.spaces.Dict(obs), gym.spaces.Discrete(int(mix["action"]["n"]))


def sizes_for(cfg: Any, mix: Dict[str, Any], minibatches: int, minibatch_rows: int) -> reference.Sizes:
    """What the reference needs of a cell, from the composed config the program runs with."""
    a = cfg.algo
    if str(a.dense_act) != "tanh" or bool(a.layer_norm) or list(a.cnn_keys.encoder):
        raise ValueError("the reference has no such path: dense_act, layer_norm or an image key differ")
    if str(a.loss_reduction) != "mean" or bool(a.anneal_lr) or float(a.optimizer.get("weight_decay", 0)) != 0:
        raise ValueError("the reference takes means, a fixed learning rate and an Adam without weight decay")
    return reference.Sizes(
        keys=tuple(a.mlp_keys.encoder), envs=int(cfg.env.num_envs), steps=int(a.rollout_steps), gamma=float(a.gamma),
        gae_lambda=float(a.gae_lambda), epochs=int(a.update_epochs), minibatches=int(minibatches), minibatch_rows=int(minibatch_rows),
        normalize_advantages=bool(a.normalize_advantages), clip_vloss=bool(a.clip_vloss), lr=float(a.optimizer.lr),
        eps=float(a.optimizer.eps), max_grad_norm=float(a.get("max_grad_norm", 0.0) or 0.0),
    )


def widths_of(cfg: Any) -> Dict[str, int]:
    """The keys of a configuration file's `widths`, from the composed config the program runs with."""
    a = cfg.algo
    return {
        "dense_units": int(a.dense_units), "mlp_layers": int(a.mlp_layers), "mlp_features_dim": int(a.encoder.mlp_features_dim),
        "rollout_steps": int(a.rollout_steps), "per_rank_batch_size": int(a.per_rank_batch_size), "update_epochs": int(a.update_epochs),
    }


def program_shapes(spec: Dict[str, Any], rehearse: bool = False) -> Tuple[Any, Dict[str, Tuple[Tuple[int, ...], Any]]]:
    """(composed config, {leaf name: (shape, dtype)}) of a cell, by
    `jax.eval_shape` over the program's own `build_agent`."""
    import jax

    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.parallel import Distributed

    from ..run import overrides_for

    cfg = compose("config", overrides_for(spec, 0, rehearse))
    obs_space, action_space = _spaces(spec["mix"])
    tree = jax.eval_shape(lambda k: build_agent(Distributed(devices=1), cfg, obs_space, action_space, k)[1], jax.random.key(0))
    return cfg, {n: (tuple(x.shape), np.dtype(x.dtype)) for n, x in flat_names(tree).items()}


def step_flops(shapes: Dict[str, Any], spec: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs of one gradient step (one minibatch) under 'total': every kernel
    is a matmul over the minibatch's rows, forward 2 FLOP a multiply-add and
    backward twice the forward. Under 'per_env_step' the player's forward for
    one env step, one row through every kernel: an on-policy loop acts for
    every row it trains on, so the whole step's share of the peak counts it."""
    rows = int(spec["config"]["widths"]["per_rank_batch_size"])
    macs = sum(float(np.prod(shape)) for name, (shape, _) in shapes.items() if name.endswith("/kernel"))
    return {"total": 6.0 * macs * rows, "per_env_step": 2.0 * macs}


def kept_bytes(shapes: Dict[str, Any], spec: Dict[str, Any]) -> Dict[str, float]:
    """What the cell keeps on the chip across calls: parameters and Adam's two moments (the rollout is the host's)."""
    params = float(sum(int(np.prod(s)) * np.dtype(d).itemsize for s, d in shapes.values()))
    return {"params": params, "adam": 2.0 * params, "total": 3.0 * params}


def rehearse(spec: Dict[str, Any], topology: Any) -> Dict[str, Any]:
    """Compile the whole update for a described chip (none attached) at the
    cell's real sizes: what it needs, and the worst case (kept state + the update's arguments and temp). Nothing runs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.ppo import make_update_fn
    from sheeprl_tpu.config import compose, instantiate
    from sheeprl_tpu.optim import clipped
    from sheeprl_tpu.parallel import Distributed

    from ..run import overrides_for

    one_chip = SingleDeviceSharding(topology.devices[0])

    def like(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)

    cfg = compose("config", overrides_for(spec, 0, False))
    obs_space, action_space = _spaces(spec["mix"])
    made = {}

    def build(key):
        made["module"], params = build_agent(Distributed(devices=1), cfg, obs_space, action_space, key)
        return params

    params = jax.eval_shape(build, jax.random.key(0))
    tx = clipped(instantiate(cfg.algo.optimizer), cfg.algo.get("max_grad_norm", 0.0))
    opt_state = jax.eval_shape(tx.init, params)
    rows = int(cfg.algo.rollout_steps) * int(cfg.env.num_envs)
    mb = int(cfg.algo.per_rank_batch_size)
    update = make_update_fn(made["module"], tx, cfg, rows // mb, mb)
    f32 = partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    data = {f"obs:{k}": f32((rows,) + tuple(v["shape"])) for k, v in spec["mix"]["observation"].items()}
    data.update({k: f32((rows, 1)) for k in ("actions", "logprobs", "values", "rewards", "dones", "returns", "advantages")})
    coefs = {k: f32(()) for k in ("clip_coef", "ent_coef", "vf_coef", "lr_frac")}
    t0 = time.time()
    compiled = update.lower(like(params), like(opt_state), like(data), like(coefs), like(jax.eval_shape(lambda: jax.random.PRNGKey(0)))).compile()
    mem = compiled.memory_analysis()
    shapes = {k: (x.shape, x.dtype) for k, x in flat_names(params).items()}
    worst = (kept_bytes(shapes, spec)["total"] + mem.temp_size_in_bytes + mem.argument_size_in_bytes) / 1e9
    return {
        "update": {"args_gb": mem.argument_size_in_bytes / 1e9, "temp_gb": mem.temp_size_in_bytes / 1e9,
                   "code_gb": mem.generated_code_size_in_bytes / 1e9, "compile_s": time.time() - t0},
        "worst_case_gb": worst,
        "flops_per_grad_step": step_flops(shapes, spec),
    }


# -- the first update against the reference -------------------------------------------------
def rollout_rows(rollout: Dict[str, np.ndarray], envs: Dict[int, Any], sz: reference.Sizes) -> Tuple[int, int, Dict[int, int]]:
    """(rows looked at, rows that differ from what the generator emitted, and
    per env the emission its last row holds). Row `t * envs + e` is step t of env e."""
    key = sz.keys[0]
    rows = wrong = 0
    last: Dict[int, int] = {}
    for t in range(sz.steps):
        for e in range(sz.envs):
            i = t * sz.envs + e
            rows += 1
            env = envs.get(e)
            got_e, n = type(env).decode(rollout[f"obs:{key}"][i]) if env is not None else (-1, -1)
            if got_e != e or not 0 <= n < env.n - 1:
                wrong += 1
                continue
            last[e] = n
            ok = (
                all(np.array_equal(rollout[f"obs:{k}"][i], env.vector(k, n)) for k in sz.keys)
                and int(rollout["actions"][i, 0]) == int(np.asarray(env.log_action[n]).reshape(-1)[0])
                # the step that answered emission n emitted n + 1: its reward and whether it closed an episode
                and float(rollout["rewards"][i, 0]) == np.float32(env.log_reward[n + 1])
                and float(rollout["dones"][i, 0]) == float(env.log_final[n + 1])
            )
            wrong += 0 if ok else 1
    return rows, wrong, last


def next_observation(envs: Dict[int, Any], last: Dict[int, int], sz: reference.Sizes) -> Dict[str, np.ndarray]:
    """What each env handed out after the rollout's last step, from the generator alone."""
    out = {k: [] for k in sz.keys}
    for e in range(sz.envs):
        env, n = envs[e], last[e] + 1
        n += int(env.log_final[n])  # a closing emission is followed by the next episode's first
        for k in sz.keys:
            out[k].append(env.vector(k, n))
    return {k: np.stack(v) for k, v in out.items()}


def decide(run: Run, envs: Dict[int, Any], spec: Dict[str, Any]) -> Tuple[Dict[str, float], Dict[str, float], Dict[str, Any]]:
    """(every number read, the limits the cell's structure gives, the detail)."""
    sz = sizes_for(run.cfg, spec["mix"], run.notes["minibatches"], run.notes["minibatch_rows"])
    values: Dict[str, float] = {}
    detail: Dict[str, Any] = {}

    rows, wrong, last = rollout_rows(run.rollout, envs, sz)
    detail["rollout"] = {"rows": rows, "wrong_rows": wrong}
    values["rollout_wrong_rows"] = float(wrong)

    # an update is owed every steps x envs env steps: never before they were taken, and the
    # player is at most the one rollout ahead that the loop's queue holds
    per_update = sz.steps * sz.envs
    exits = np.sort(np.concatenate([np.asarray(e.t_exit) for e in envs.values()]))
    enters = np.sort(np.concatenate([np.asarray(e.t_enter) for e in envs.values()]))
    calls_t = np.asarray(run.calls_t)
    owed = per_update * np.arange(1, len(calls_t) + 1)
    values["update_early_steps"] = float(np.max(owed - np.searchsorted(exits, calls_t, side="right")))
    values["update_late_steps"] = float(np.max(np.searchsorted(enters, calls_t, side="right") - owed))
    structural = {"rollout_wrong_rows": 0.0, "update_early_steps": 0.0, "update_late_steps": 2.0 * per_update}

    if wrong == 0 and len(last) == sz.envs:
        t0 = time.perf_counter()
        ref = reference.first_update(make_weights(run.seed, run.shapes), run.rollout, next_observation(envs, last, sz),
                                     run.coefs, run.update_key, sz, params_after=run.params_after)
        for name in ("values", "logprobs", "advantages"):
            scale = float(np.median(np.abs(ref[name]))) + 1e-6
            values[f"{name}_gap"] = float(np.max(np.abs(run.rollout[name] - ref[name]))) / scale
        for name, want in ref["losses"].items():
            short = name.split("/")[1].replace("_loss", "")
            values[f"loss_gap_{short}"] = abs(run.losses[name] - want) / (abs(want) + 0.1)
        values["update_gap"], values["update_mid"], detail["worst_leaf"] = gap_by_leaf(ref["program_delta"], ref["delta"])
        detail["losses_program_reference"] = {k: [run.losses[k], v] for k, v in ref["losses"].items()}
        detail["reference_s"] = time.perf_counter() - t0
    return values, structural, detail


# -- the fault the tests plant (never used by a benchmark run) ------------------------------
@contextlib.contextmanager
def faults(kind: str):
    """The timed path broken beneath the harness's own wrappers: `unchanged`
    is an update that returns its parameters and optimizer state as it got them."""
    from sheeprl_tpu.algos.ppo import ppo

    if kind != "unchanged":
        raise ValueError(f"unknown fault {kind!r}")
    orig = ppo.make_update_fn

    def make_update_fn(*args: Any, **kwargs: Any):
        import jax
        import jax.numpy as jnp

        update = orig(*args, **kwargs)

        def unchanged(params, opt_state, data, coefs, key):
            kept = jax.tree.map(jnp.copy, (params, opt_state))  # the update donates what it is given
            return (*kept, update(params, opt_state, data, coefs, key)[2])

        return unchanged

    ppo.make_update_fn = make_update_fn
    try:
        yield
    finally:
        ppo.make_update_fn = orig
