"""`ops/wgrad_hoist.py`: the world model's sequence scan computes the gradient
of every Dense kernel it applies once, after the backward scan (PERF.md, PR 35).

* the gradients are autodiff's of the plain scan (the helper alone, and the
  whole DreamerV3 train step: coupled, decoupled, `bf16-mixed`, an episode
  that begins mid-sequence);
* no scan of the train step's gradient carries an array of a kernel's shape:
  the test that fails when the accumulators come back;
* the parameter tree is the parent's path for path, and without the two
  collections a `HoistableDense` is `nn.Dense` bit for bit.
"""
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict, unflatten_dict

from dreamer_tiny import N_ACT, make_trainer
from sheeprl_tpu.ops import wgrad_hoist

RSSM = {"coupled": [], "decoupled": ["algo.world_model.decoupled_rssm=True"]}
T, B = 5, 3  # no [B, width] carry has the shape of a tiny kernel
HOISTED = wgrad_hoist.scan  # the tests below put `plain_scan` in its place for the control


def plain_scan(step, params, carry0, xs, held_xs=(), report=None):
    """`lax.scan` of the same step, differentiated by autodiff alone."""

    def body(carry, scanned):
        carry, y, _ = step(params, None, carry, *scanned)
        return carry, y

    return jax.lax.scan(body, carry0, (xs, held_xs))


# ---------------------------------------------------------------- the helper alone


class Cell(nn.Module):
    """One Dense applied twice a step (as the RSSM's transition head is, to
    the initial state and to the new one), one without a bias, a LayerNorm
    whose vectors stay with the scan, an episode start and a sampled output."""

    @nn.compact
    def __call__(self, h, x, first, key):
        dense = wgrad_hoist.HoistableDense(8, name="Dense_0")
        start = jnp.tanh(dense(jnp.ones((h.shape[0], h.shape[1] + x.shape[1]))))
        h = (1 - first) * h + first * start
        h = jnp.tanh(dense(jnp.concatenate([h, x], -1)))
        y = wgrad_hoist.HoistableDense(3, use_bias=False, name="out")(nn.LayerNorm()(h))
        return h, y + 0.1 * jax.random.normal(key, y.shape)


def cell_problem():
    cell = Cell()
    key = jax.random.key(0)
    xs = jax.random.normal(key, (T, B, 5))
    first = jnp.zeros((T, B, 1)).at[3, 1].set(1.0)
    keys = jax.random.split(key, T)
    h0 = jnp.zeros((B, 8))
    params = cell.init(key, h0, xs[0], first[0], keys[0])["params"]

    def step(params, perturbations, h, x, held):
        variables = {"params": params}
        if perturbations is not None:
            variables[wgrad_hoist.PERTURB] = perturbations
        (h, y), taped = cell.apply(variables, h, x, *held, mutable=[wgrad_hoist.TAPE])
        return h, y, taped[wgrad_hoist.TAPE]

    def loss(scan, params, h0, xs):
        h, ys = scan(step, params, h0, xs, (first, keys))
        return (ys ** 2).sum() + h.sum()

    return loss, params, h0, xs


def relative_gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(a).max(), 1e-30))


def test_init_of_a_hoistable_dense_makes_parameters_only():
    cell = Cell()
    variables = cell.init(jax.random.key(0), jnp.zeros((B, 8)), jnp.zeros((B, 5)), jnp.zeros((B, 1)), jax.random.key(1))
    assert set(variables) == {"params"}
    assert {k: v.shape for k, v in flatten_dict(variables["params"]).items()} == {
        ("Dense_0", "kernel"): (13, 8), ("Dense_0", "bias"): (8,), ("out", "kernel"): (8, 3),
        ("LayerNorm_0", "scale"): (8,), ("LayerNorm_0", "bias"): (8,),
    }


def test_helper_value_and_every_gradient_are_autodiffs_of_the_plain_scan():
    loss, params, h0, xs = cell_problem()
    want_v, want = jax.value_and_grad(loss, argnums=(1, 2, 3))(plain_scan, params, h0, xs)
    got_v, got = jax.jit(jax.value_and_grad(loss, argnums=(1, 2, 3)), static_argnums=0)(wgrad_hoist.scan, params, h0, xs)
    assert float(got_v) == pytest.approx(float(want_v), rel=1e-6)
    assert float(loss(wgrad_hoist.scan, params, h0, xs)) == pytest.approx(float(want_v), rel=1e-6)  # undifferentiated
    gaps = jax.tree.map(relative_gap, want, got)
    assert max(jax.tree.leaves(gaps)) <= 1e-5, gaps
    assert all(np.abs(np.asarray(g)).max() > 0 for g in jax.tree.leaves(got))


def test_helper_reports_the_kernels_it_hoists_and_the_rows_of_a_contraction():
    loss, params, h0, xs = cell_problem()
    seen = []

    def scan(*args):
        return wgrad_hoist.scan(*args, report=seen.append)

    jax.grad(loss, argnums=1)(scan, params, h0, xs)
    assert seen == [{"kernels": 2, "kernel_bytes": 4 * (13 * 8 + 8 * 3), "rows": T * B}]


def scan_carries(jaxpr, inside=False, out=None):
    """Shapes of the carries of every scan NESTED in another scan's body (the
    outermost is `train`'s own loop over G, which carries the state)."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        is_scan = eqn.primitive.name == "scan"
        if is_scan and inside:
            n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
            out.extend(tuple(v.aval.shape) for v in eqn.invars[n_consts : n_consts + n_carry])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            scan_carries(sub, inside or is_scan, out)
    return out


def test_helper_backward_scan_carries_the_state_and_vectors_only():
    loss, params, h0, xs = cell_problem()

    def carries(scan):
        nested = lambda p: jax.lax.scan(lambda c, _: (c, jax.grad(loss, argnums=1)(scan, p, h0, xs)), 0, None, length=1)  # noqa: E731
        return scan_carries(jax.make_jaxpr(nested)(params).jaxpr)

    assert {(13, 8), (8, 3)} <= set(carries(plain_scan))  # autodiff's accumulators: the control
    assert set(carries(wgrad_hoist.scan)) == {(B, 8), (8,)}


# ---------------------------------------------------------------- DreamerV3's train step


def tiny_batch():
    rng = np.random.default_rng(0)
    is_first = np.zeros((1, T, B, 1), np.float32)
    is_first[:, 2, 1] = 1.0  # an episode begins mid-sequence
    return {
        "rgb": jnp.asarray(rng.integers(0, 255, (1, T, B, 64, 64, 3), np.uint8)),
        "actions": jnp.asarray(np.eye(N_ACT, dtype=np.float32)[rng.integers(0, N_ACT, (1, T, B))]),
        "rewards": jnp.asarray(rng.standard_normal((1, T, B, 1)), jnp.float32),
        "terminated": jnp.zeros((1, T, B, 1), jnp.float32),
        "truncated": jnp.zeros((1, T, B, 1), jnp.float32),
        "is_first": jnp.asarray(is_first),
    }


def first_moments(overrides, scan, monkeypatch):
    """Adam's first moment of the world model after ONE gradient step from
    zero moments: 0.1 x the clipped gradient, leaf for leaf, so two programs'
    moments compare as their world-model gradients do."""
    monkeypatch.setattr(wgrad_hoist, "scan", scan)
    train, params, opt_states, moments = make_trainer(overrides)
    _, opt_states, _, metrics = train(params, opt_states, moments, tiny_batch(), jax.random.split(jax.random.key(7), 1))
    mus = [s.mu for s in jax.tree.leaves(opt_states["wm"], is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    assert len(mus) == 1
    return {"/".join(k): np.asarray(v) for k, v in flatten_dict(mus[0]).items()}, float(metrics["Loss/world_model_loss"][0])


# bf16-mixed: autodiff rounds each step's kernel gradient to bfloat16 at the cast
# boundary before it adds it (2.3e-3 read here, one part in 2**9), the contraction accumulates all rows in float32
@pytest.mark.parametrize("precision, tolerance", [("32-true", 1e-5), ("bf16-mixed", 8e-3)])
@pytest.mark.parametrize("rssm", sorted(RSSM))
def test_world_model_gradient_is_autodiffs_of_the_plain_scan(rssm, precision, tolerance, monkeypatch):
    overrides = RSSM[rssm] + [f"fabric.precision={precision}"]
    got, got_loss = first_moments(overrides, HOISTED, monkeypatch)
    want, want_loss = first_moments(overrides, plain_scan, monkeypatch)
    assert got_loss == want_loss  # the forward is the same program
    assert set(got) == set(want) and any(k.startswith("rssm/") and k.endswith("/kernel") for k in got)
    gaps = {k: relative_gap(want[k], got[k]) for k in want}
    assert max(gaps.values()) <= tolerance, {k: g for k, g in gaps.items() if g > tolerance}
    assert all(np.abs(got[k]).max() > 0 for k in got if k.startswith("rssm/")), "a gradient was left out"


@pytest.mark.parametrize("rssm", sorted(RSSM))
def test_no_scan_of_the_train_step_carries_an_array_of_a_kernels_shape(rssm, monkeypatch):
    def carries(scan):
        monkeypatch.setattr(wgrad_hoist, "scan", scan)
        train, params, opt_states, moments = make_trainer(RSSM[rssm])
        kernels = {tuple(v.shape) for k, v in flatten_dict(params["wm"]).items() if v.ndim >= 2}
        jaxpr = jax.make_jaxpr(train)(params, opt_states, moments, tiny_batch(), jax.random.split(jax.random.key(0), 1))
        found = set(scan_carries(jaxpr.jaxpr))
        assert found, "the train step has scans nested in its loop over G"
        return found & kernels

    assert carries(plain_scan), "the control: autodiff's transpose carries its kernels' accumulators"
    assert carries(HOISTED) == set()


# the world model's tree at `dreamer_tiny` widths as the parent (ba93de6) builds it: path -> shape
PARENT_RSSM_TREE = {
    "initial_recurrent_state": [8],
    "recurrent_model/LayerNorm_0/LayerNorm_0/bias": [16],
    "recurrent_model/LayerNorm_0/LayerNorm_0/scale": [16],
    "recurrent_model/gru/LayerNorm_0/LayerNorm_0/bias": [24],
    "recurrent_model/gru/LayerNorm_0/LayerNorm_0/scale": [24],
    "recurrent_model/gru/fused/kernel": [24, 24],
    "recurrent_model/mlp/kernel": [20, 16],
    "representation/Dense_0/kernel": [264, 16],
    "representation/LayerNorm_0/LayerNorm_0/bias": [16],
    "representation/LayerNorm_0/LayerNorm_0/scale": [16],
    "representation/logits/bias": [16],
    "representation/logits/kernel": [16, 16],
    "transition/Dense_0/kernel": [8, 16],
    "transition/LayerNorm_0/LayerNorm_0/bias": [16],
    "transition/LayerNorm_0/LayerNorm_0/scale": [16],
    "transition/logits/bias": [16],
    "transition/logits/kernel": [16, 16],
}
PARENT_TREE_DIGEST = {"coupled": (67, 33173), "decoupled": (67, 33045)}  # leaves, values of the whole agent


@pytest.mark.parametrize("rssm", sorted(RSSM))
def test_build_agent_keeps_the_parents_parameter_tree(rssm):
    _, params, _, _ = make_trainer(RSSM[rssm])
    tree = {"/".join(k): list(v.shape) for k, v in flatten_dict(params["wm"]["rssm"]).items()}
    want = dict(PARENT_RSSM_TREE)
    if rssm == "decoupled":
        want["representation/Dense_0/kernel"] = [256, 16]  # the posterior reads the embedding alone
    assert tree == want
    leaves = jax.tree.leaves(params)
    assert (len(leaves), sum(int(v.size) for v in leaves)) == PARENT_TREE_DIGEST[rssm]


def tiny_world_model(rssm):
    import gymnasium as gym

    from dreamer_tiny import TINY_DV3
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.parallel import Distributed

    cfg = compose("config", TINY_DV3 + RSSM[rssm])
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8)})
    wm, _, _, params = build_agent(Distributed(devices=1), cfg, obs_space, [N_ACT], False, jax.random.key(0))
    return wm, {"params": params["wm"]}


@pytest.mark.parametrize("rssm", sorted(RSSM))
def test_without_the_collections_the_one_step_methods_are_the_plain_denses_bit_for_bit(rssm, monkeypatch):
    """`RSSM.dynamic` / `imagination` as the player, `serve` and `fleet` call
    them: no collection, so what `nn.Dense` computes. The tape alone (the
    probe) and zero perturbations do not move a bit either."""
    from sheeprl_tpu.algos.dreamer_v3 import agent
    from sheeprl_tpu.algos.dreamer_v3.agent import WorldModel
    from sheeprl_tpu.models import models

    wm, variables = tiny_world_model(rssm)
    key = jax.random.key(3)
    h = jax.random.normal(key, (B, 8))
    z = jax.nn.one_hot(jax.random.randint(key, (B, 4), 0, 4), 4).reshape(B, 16)
    a = jax.nn.one_hot(jnp.arange(B) % N_ACT, N_ACT)
    first = jnp.zeros((B, 1)).at[1].set(1.0)
    if rssm == "decoupled":
        method, args = WorldModel.dynamic_decoupled, (z, h, a, first)
    else:
        method, args = WorldModel.dynamic, (z, h, a, jax.random.normal(key, (B, 256)), first, key)

    def outputs():  # `setup` and the compact bodies look the Dense up when they run
        return wm.apply(variables, *args, method=method), wm.apply(variables, z, h, a, key, method=WorldModel.imagination)

    got = outputs()
    probed, taped = wm.apply(variables, *args, method=method, mutable=[wgrad_hoist.TAPE])
    tape = flatten_dict(taped[wgrad_hoist.TAPE])
    zeros = unflatten_dict({k: jax.tree.map(jnp.zeros_like, v) for k, v in tape.items() if k[-1] == "outputs"})
    perturbed, _ = wm.apply({**variables, wgrad_hoist.PERTURB: zeros}, *args, method=method, mutable=[wgrad_hoist.TAPE])
    monkeypatch.setattr(agent, "HoistableDense", nn.Dense)
    monkeypatch.setattr(models, "HoistableDense", nn.Dense)
    want = outputs()

    def same_bits(x, y):
        return all(np.array_equal(np.asarray(p), np.asarray(q)) for p, q in zip(jax.tree.leaves(x), jax.tree.leaves(y), strict=True))

    assert same_bits(got, want) and same_bits(got[0], probed) and same_bits(got[0], perturbed)
    # the transition head runs twice in a step (on the initial state and on the new one): two taped calls
    calls = {"/".join(k[:-1]): len(v) for k, v in tape.items() if k[-1] == "inputs"}
    assert calls["rssm/transition/logits"] == 2 and calls["rssm/recurrent_model/gru/fused"] == 1
