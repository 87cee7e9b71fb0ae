"""The block-diffusion backbone of the recurrent on-policy loop
(`models/block_diffusion.py`, `algos/ppo_recurrent/block_diffusion_policy.py`)
at a small size with every mechanism on: hidden 64, 2 layers, 4 query and 2
KV heads of 16, 8 experts with 4 held and top-2 softmax routing, no shared
expert, a vocabulary of 97, blocks of L = 4 ids committed in S = 2 denoising
forwards, a cache of 16 positions (4 blocks), an episode start inside the
rollout. The program against the benchmark's plain reference
(`perfbench/references/ppo_recurrent_diffusion.py`) on seeded weights.

Tolerances: both sides compute in float32 on the CPU, so they differ by the
order of the sums alone. Logits and values are held to 2e-4 of their scale
(the program's two-part softmax and grouped expert sum against one masked
square and a dense sum, a few float32 roundings per layer); gradients to 1e-3
(a backward pass sums over every row again); one Adam step to 2e-3 of the
largest move of each leaf (Adam divides by the root of the second moment,
which magnifies a rounding where a gradient is near 0)."""
import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import flatten, make_weights, nest
from perfbench.references import ppo_recurrent_diffusion as reference
from sheeprl_tpu.models import block_diffusion as bd
from sheeprl_tpu.models import sequence as seq

TINY = [
    "exp=ppo_recurrent_sdar", "env.num_envs=3", "algo.rollout_steps=4", "algo.per_rank_num_batches=1", "algo.update_epochs=1",
    "algo.total_steps=24",
    "algo.backbone.hidden_size=64", "algo.backbone.num_attention_heads=4", "algo.backbone.num_key_value_heads=2", "algo.backbone.head_dim=16",
    "algo.backbone.moe_intermediate_size=32", "algo.backbone.n_routed_experts=8", "algo.backbone.num_experts_per_tok=2",
    "algo.backbone.experts_held=4", "algo.backbone.first_expert=2", "algo.backbone.num_hidden_layers=2", "algo.backbone.vocab_size=97",
    "env.wrapper.n_steps=3", "checkpoint.save_last=False", "checkpoint.every=1000000", "model_manager.disabled=True", "metric.log_every=12",
    "buffer.memmap=False",
]
B, T, L, S, SEED = 3, 4, 4, 2, 3000000029


@pytest.fixture(scope="module")
def small():
    """(composed config, the policy, the reference's Sizes, seeded weights, a rollout [B, T, ...] with a reset inside)."""
    from sheeprl_tpu.algos.ppo_recurrent.agent import BlockDiffusionPolicy
    from sheeprl_tpu.config import compose

    cfg = compose("config", TINY)
    scfg = seq.SequenceConfig.from_node(cfg.algo.backbone)
    module = BlockDiffusionPolicy(scfg, "block", L, S)
    sz = reference.Sizes(heads=4, kv_heads=2, head_dim=16, experts=8, top_k=2, experts_held=4, first_expert=2, layers=2, theta=1e6,
                         norm_eps=1e-6, block=L, steps=S, mask_id=module.mask_id, envs=B, blocks=T, gamma=0.99, gae_lambda=0.95, epochs=1,
                         minibatches=1, minibatch_seqs=B, normalize_advantages=True, clip_vloss=False, lr=float(cfg.algo.optimizer.lr),
                         eps=float(cfg.algo.optimizer.eps), max_grad_norm=float(cfg.algo.max_grad_norm))
    tree = jax.eval_shape(lambda k: bd.init_params(scfg, k), jax.random.key(0))
    shapes = {n: (tuple(x.shape), x.dtype) for n, x in flatten(tree).items()}
    k = jax.random.split(jax.random.key(4), 3)
    rollout = {"tokens": jax.random.randint(k[0], (B, T, L), 0, module.mask_id), "actions": jax.random.randint(k[1], (B, T, L), 0, module.mask_id),
               "order": jnp.argsort(jax.random.uniform(k[2], (B, T, L)), -1).argsort(-1) // (L // S),
               "is_first": jnp.zeros((B, T), bool).at[:, 0].set(True).at[1, 2].set(True).at[2, 1].set(True)}
    return cfg, module, sz, shapes, rollout


def weights(shapes):
    return nest(dict(make_weights(SEED, shapes)))


def close(a, b, tol=2e-4):
    """Within `tol` of `b`'s scale; the mask id's logit (NEG) is held equal, and is no part of the scale."""
    a, b = np.asarray(a), np.asarray(b)
    live = np.abs(b) < 1e29
    assert a.shape == b.shape and np.array_equal(a[~live], b[~live])
    err = float(np.max(np.abs(a - b)[live], initial=0.0))
    assert err <= tol * (1.0 + np.max(np.abs(b[live]), initial=0.0)), err


def reference_states(params, rollout, sz, od=None):
    """The reference's final states of its concatenated layout: (clean rows [B, T, L, C], copies [B, T, S, L, C])."""
    ids, positions, mask = reference.layout(rollout["tokens"], rollout["actions"], rollout["order"], rollout["is_first"], sz)
    X = params["embed"]["embedding"][ids]
    for i in range(sz.layers):
        X = reference.layer(params[f"layer_{i}"], X, positions, mask, sz, od)
    return X[:, :T * L].reshape(B, T, L, -1), X[:, T * L:].reshape(B, T, S, L, -1)


def reference_logits(params, x, sz, od=None):
    logits = reference.mm(reference.rms(x, params["final_norm"]["scale"], sz.norm_eps), params["head"]["kernel"], od)
    return jnp.where(jnp.arange(logits.shape[-1]) == sz.mask_id, reference.NEG, logits)


def test_a_reference_at_bfloat16_operands_fails_the_logits_tolerance(small):
    """The control: the same forward with every matmul operand rounded to bfloat16 lies outside 2e-4, so the
    tolerance the tests hold the program to tells float32 from the precision below it."""
    _, _, sz, shapes, r = small
    params = weights(shapes)
    logits = jax.jit(lambda p, od: reference_logits(p, reference_states(p, r, sz, od)[1], sz, od), static_argnums=1)
    with pytest.raises(AssertionError):
        close(logits(params, jnp.bfloat16), logits(params, None))


def test_decoding_through_the_cache_gives_the_references_logits_and_values_at_every_denoising_step(small):
    """Forward 1 over [observed block ; masked block], its keys written; forward 2 over the partly filled block; per
    block, against the reference's full forward over the concatenated layout."""
    _, module, sz, shapes, r = small
    params = weights(shapes)
    clean, copies = jax.jit(lambda p: reference_states(p, r, sz))(params)
    want_values = reference.mm(reference.rms(clean[:, :, L - 1], params["final_norm"]["scale"], sz.norm_eps), params["value"]["kernel"], None)[..., 0]
    want_values = want_values + params["value"]["bias"][0]
    want = jax.jit(lambda p, x: reference_logits(p, x, sz))(params, copies)  # [B, T, S, L, V]
    scfg, mask = module.cfg, module.mask_id
    ones, none = jnp.ones((L, L), bool), jnp.zeros((L, L), bool)
    step = jax.jit(bd.forward_decode, static_argnames=("cfg", "mask_id", "write"))
    cache = bd.new_cache(scfg, B, T * L)
    start = jnp.zeros((B,), jnp.int32)
    for t in range(T):
        base = jnp.int32(t * L)
        start = jnp.where(r["is_first"][:, t], base, start)
        masked = jnp.full((B, L), mask, jnp.int32)
        own = jnp.block([[ones, none], [ones, ones]])
        logits, values, cache = step(params, cache, jnp.concatenate([r["tokens"][:, t], masked], 1), base + jnp.arange(2 * L), own, base,
                                     start, scfg, mask, write=L)
        close(logits[:, L:], want[:, t, 0])
        close(values[:, L - 1], want_values[:, t])
        partial_block = jnp.where(r["order"][:, t] < 1, r["actions"][:, t], mask)
        logits = step(params, cache, partial_block, base + L + jnp.arange(L), ones, base + L, start, scfg, mask)[0]
        close(logits, want[:, t, 1])
    assert np.all(np.asarray(want[..., mask]) == reference.NEG)


def test_the_stored_log_probability_and_value_are_the_references_trajectory_ones(small):
    """The policy's own act, two rollouts (the second after the restart), every block's stored log-probability (the
    sum over both steps of the committed ids') and value against the reference's recomputation from the rollout's
    observed blocks, actions and `order`; the committed ids are never the mask and each step commits L / S ids."""
    from sheeprl_tpu.algos.ppo_recurrent import block_diffusion_policy as bdp
    from sheeprl_tpu.algos.ppo_recurrent import sequence_policy as sp

    _, module, sz, shapes, r = small
    params = weights(shapes)
    act = bdp.make_act_fn(module)
    state = bdp.new_state(module, B, T)
    for rollout in range(2):
        state = sp.restart(state)
        actions = []
        for t in range(T):
            block, state = act(params, state, r["tokens"][:, t], r["is_first"][:, t] | (t == 0), jax.random.key(rollout))
            actions.append(block)
        actions = jnp.stack(actions, 1)
        order = jnp.swapaxes(state["order"], 0, 1)
        assert int(jnp.max(actions)) < module.mask_id and np.all(np.sum(np.asarray(order) == 0, -1) == L // S)
        got = {"tokens": r["tokens"], "actions": actions, "order": order, "is_first": r["is_first"].at[:, 0].set(True)}
        want_logp, _, want_values = jax.jit(lambda p: reference.forward(p, got["tokens"], got["actions"], got["order"], got["is_first"], sz))(params)
        close(jnp.swapaxes(state["logprobs"], 0, 1), want_logp)
        close(jnp.swapaxes(state["values"], 0, 1), want_values)
        assert int(state["cache"]["pos"]) == T * L


def test_the_updates_loss_gradients_and_one_adam_step_agree_with_the_reference(small):
    from sheeprl_tpu.algos.ppo_recurrent import block_diffusion_policy as bdp
    from sheeprl_tpu.config import instantiate
    from sheeprl_tpu.optim import clipped

    cfg, module, sz, shapes, r = small
    params = weights(shapes)
    k = jax.random.split(jax.random.key(5), 4)
    mb = {**r, "logprobs": -18.3 + 0.1 * jax.random.normal(k[0], (B, T)), "values": jax.random.normal(k[1], (B, T)),
          "returns": jax.random.normal(k[2], (B, T)), "advantages": jax.random.normal(k[3], (B, T))}
    coefs = {n: jnp.float32(v) for n, v in {"clip_coef": 0.2, "ent_coef": 0.01, "vf_coef": 0.2, "lr_frac": 1.0}.items()}
    (_, want_losses), want_grads = reference._programs(sz, None)["whole"](params, mb, coefs)
    by_layer_losses, by_layer = reference.grads_by_layer(params, mb, coefs, sz)
    for n, g in flatten(want_grads).items():
        close(flatten(by_layer)[n], g, 1e-5)  # a layer at a time is the whole gradient
    assert {n: float(v) for n, v in by_layer_losses.items()} == pytest.approx({n: float(v) for n, v in want_losses.items()}, rel=1e-5)

    def program_loss(p):
        logits, values, _ = bd.forward_train(p, mb["tokens"], mb["is_first"], mb["actions"], mb["order"], module.cfg, S, module.mask_id)
        logp = jax.nn.log_softmax(logits)
        taken = jnp.sum(jnp.take_along_axis(logp, mb["actions"][..., None], -1)[..., 0], -1)
        return reference.losses_of(taken, -jnp.sum(jnp.exp(logp) * logp, (-2, -1)), values, mb, coefs, sz)[0]

    got_grads = jax.jit(jax.grad(program_loss))(params)
    for n, g in flatten(want_grads).items():
        close(flatten(got_grads)[n], g, 1e-3)

    tx = clipped(instantiate(cfg.algo.optimizer), cfg.algo.get("max_grad_norm", 0.0))
    update = bdp.make_update_fn(module, tx, cfg, 1, B)
    key = jax.random.key(7)
    got_params, _, means, report = update(jax.tree.map(jnp.copy, params), tx.init(params), mb, coefs, key)
    for name, want in want_losses.items():
        assert float(report["losses"][name][0, 0]) == float(means[name]) == pytest.approx(float(want), rel=2e-4, abs=2e-5), name
    after, steps = reference.first_update(dict(make_weights(SEED, shapes)), {n: np.asarray(v) for n, v in mb.items()}, coefs,
                                          np.asarray(jax.random.key_data(key)), sz, whole=True)
    before = make_weights(SEED, shapes)
    for n, v in flatten(got_params).items():
        moved = float(jnp.max(jnp.abs(after[n] - before[n])))
        assert float(jnp.max(jnp.abs(v - after[n]))) <= 2e-3 * moved + 1e-7, n
    assert len(steps) == 1 and int(np.sum(report["load"]["dropped"])) == 0


def test_the_mask_keeps_each_copy_off_every_other_copy_and_off_later_blocks(small):
    """What a block's logits and value may depend on, by perturbation of the program's update forward: block t's
    read-outs do not move when a later block's observed ids or another block's actions change; no read-out moves
    when an id committed at the last step changes (it stands in no copy); changing an id committed at step 0 moves
    the read-outs of step 1's positions of that block (copy 1 holds it) and not those of step 0's (copy 0 does not)."""
    _, module, _, shapes, r = small
    params = weights(shapes)
    fwd = jax.jit(lambda t, a, o: bd.forward_train(params, t, r["is_first"], a, o, module.cfg, S, module.mask_id, remat=False)[:2])
    logits, values = fwd(r["tokens"], r["actions"], r["order"])
    e, t = 0, 1
    later = r["tokens"].at[e, t + 1:].set((r["tokens"][e, t + 1:] + 7) % module.mask_id)
    other = r["actions"].at[e, t + 1].set((r["actions"][e, t + 1] + 5) % module.mask_id).at[e, t - 1].set((r["actions"][e, t - 1] + 5) % module.mask_id)
    lg, v = fwd(later, other, r["order"])
    assert np.array_equal(np.asarray(lg[e, t]), np.asarray(logits[e, t])) and np.array_equal(np.asarray(v[e, :t + 1]), np.asarray(values[e, :t + 1]))
    order = np.asarray(r["order"][e, t])
    last = r["actions"].at[e, t].set(jnp.where(r["order"][e, t] == S - 1, (r["actions"][e, t] + 3) % module.mask_id, r["actions"][e, t]))
    lg, v = fwd(r["tokens"], last, r["order"])
    assert np.array_equal(np.asarray(lg), np.asarray(logits)) and np.array_equal(np.asarray(v), np.asarray(values))
    first = r["actions"].at[e, t].set(jnp.where(r["order"][e, t] == 0, (r["actions"][e, t] + 3) % module.mask_id, r["actions"][e, t]))
    lg, _ = fwd(r["tokens"], first, r["order"])
    moved = np.max(np.abs(np.asarray(lg[e, t]) - np.asarray(logits[e, t])), -1)  # per position of block t
    assert np.all(moved[order == 0] == 0.0) and np.all(moved[order == 1] > 0.0)


def test_the_later_blocks_never_see_a_copy(small):
    """A copy of block t is no key of any later row: the read-outs of blocks after t stay where block t's committed
    ids change but its observed ids do not."""
    _, module, _, shapes, r = small
    params = weights(shapes)
    fwd = jax.jit(lambda a: bd.forward_train(params, r["tokens"], r["is_first"], a, r["order"], module.cfg, S, module.mask_id, remat=False)[:2])
    logits, values = fwd(r["actions"])
    lg, v = fwd(r["actions"].at[:, 1].set((r["actions"][:, 1] + 11) % module.mask_id))
    assert np.array_equal(np.asarray(lg[:, 2:]), np.asarray(logits[:, 2:])) and np.array_equal(np.asarray(v), np.asarray(values))


def test_the_kernel_form_of_the_updates_attention_gives_the_xla_forms_values_and_gradients(small):
    """`train_attention_kernel` through the splash kernel's interpreter against `train_attention`, on the output and
    on the gradients of q, k and v: 8 query heads over 2 KV heads of 128, T 8 blocks of L 4 and S 2 copies (96 rows,
    padded to 128), sequence 0 one episode from its first block, sequence 1 a second episode from block 5. Both are
    float32 on the CPU; they differ by the order of the sums."""
    b, t, l, s, h, kv, d = 2, 8, 4, 2, 8, 2, 128
    rows = (1 + s) * t * l
    cfg = small[1].cfg._replace(head_dim=d)  # the scale reads the head's width alone
    k = jax.random.split(jax.random.key(21), 4)
    q, key, val = (jax.random.normal(k[0], (b, rows, h, d)), jax.random.normal(k[1], (b, rows, kv, d)), jax.random.normal(k[2], (b, rows, kv, d)))
    w = jax.random.normal(k[3], (b, rows, h * d))
    episode = jnp.cumsum(jnp.zeros((b, t), bool).at[:, 0].set(True).at[1, 5].set(True), 1)
    block = jnp.arange(t)
    sees, seen = jnp.concatenate([jnp.repeat(block, l), jnp.repeat(block, s * l)]), jnp.repeat(block, l)
    mask = (seen[None, None, :] <= sees[None, :, None]) & (episode[:, sees][:, :, None] == episode[:, seen][:, None, :])
    forms = {"xla": lambda q, k, v: bd.train_attention(q, k, v, mask, t * l, l, cfg),
             "kernel": lambda q, k, v: bd.train_attention_kernel(q, k, v, episode[:, sees], t, l, s, cfg, interpret=True)}
    got = {n: jax.jit(jax.value_and_grad(lambda q, k, v: jnp.sum(f(q, k, v) * w), (0, 1, 2)))(q, key, val) for n, f in forms.items()}
    outs = {n: jax.jit(f)(q, key, val) for n, f in forms.items()}
    close(outs["kernel"], outs["xla"], 1e-5)
    for g_kernel, g_xla in zip(got["kernel"][1], got["xla"][1]):
        close(g_kernel, g_xla, 1e-5)


def test_the_kernels_mask_is_the_two_parts_and_the_resolver_keeps_the_xla_form_on_the_cpu():
    """`kernel_mask` over the real rows is the clean part's block-causal mask beside each copy's own block (the
    episode is the segment ids'); a pad row sees itself alone. Off a TPU `auto` resolves to the XLA form."""
    t, l, s = 8, 4, 2
    m = bd.kernel_mask(t, l, s)
    clean, rows = t * l, (1 + s) * t * l
    assert m.shape == (128, 128) and bd.kernel_rows(t, l, s) == 128
    sees = np.concatenate([np.repeat(np.arange(t), l), np.repeat(np.arange(t), s * l)])
    assert np.array_equal(m[:rows, :clean], np.arange(clean)[None, :] // l <= sees[:, None])
    copies = np.kron(np.eye(s * t, dtype=bool), np.ones((l, l), bool))
    assert np.array_equal(m[:rows, clean:rows], np.vstack([np.zeros((clean, s * t * l), bool), copies]))
    assert np.array_equal(m[rows:], np.eye(128, dtype=bool)[rows:]) and not m[:rows, rows:].any()
    assert bd.attn_blocks_computed(t, l, s) == 1.0  # one tile of 128 rows
    assert bd.resolve_train_attention() == "xla" and bd.resolve_train_attention("kernel") == "kernel"
    with pytest.raises(ValueError, match="auto"):
        bd.resolve_train_attention("splash")


@pytest.mark.parametrize("grouped", [True, False], ids=["grouped", "acting_form"])
def test_the_expert_shares_add_up_to_the_whole_layer_with_softmax_routing_and_no_shared_expert(small, grouped):
    _, module, sz, shapes, _ = small
    scfg = module.cfg
    p = weights(shapes)["layer_1"]["moe"]
    assert set(p) == {"router", "experts"}  # softmax routing chooses by score alone; no shared expert
    full = {n: jax.random.normal(jax.random.fold_in(jax.random.key(11), i), (8, *v["kernel"].shape[1:])) * 0.1
            for i, (n, v) in enumerate(p["experts"].items())}
    u = jax.random.normal(jax.random.key(12), (B * T * L, scfg.hidden_size))
    whole, _ = reference.experts({**p, "experts": {n: {"kernel": k} for n, k in full.items()}}, u, sz._replace(experts_held=8, first_expert=0), None)
    parts = 0.0
    for first in range(0, 8, 4):
        share = {**p, "experts": {n: {"kernel": k[first: first + 4]} for n, k in full.items()}}
        y, load = seq.moe(share, u, scfg._replace(first_expert=first), grouped=grouped)
        parts = parts + y
        assert int(load["dropped"]) == 0
    close(parts, whole)


FIT_TOKENS = 256  # rows of the update-form tests: with 2 of the 8 experts held, the load-sized buffer (256) is half the worst case's


@pytest.fixture(scope="module")
def seeded(small):
    """The fixture's seeded weights, made once for the tests below."""
    return weights(small[3])


def two_held(p, scfg, routing, steer):
    """The fixture's layer cut to its first 2 held experts under `routing` (`sigmoid`, with an `e_bias` to choose by,
    or `softmax`) and FIT_TOKENS rows of input; with `steer`, one input column the router reads as +50 for a held expert
    and -50 for the others."""
    scfg = scfg._replace(experts_held=2, scoring_func=routing)
    p = {"router": p["router"], "experts": {n: {"kernel": k["kernel"][:2]} for n, k in p["experts"].items()}}
    if routing == "sigmoid":
        p["e_bias"] = 0.1 * jax.random.normal(jax.random.key(18), (scfg.n_routed_experts,))
    u = jax.random.normal(jax.random.key(17), (FIT_TOKENS, scfg.hidden_size))
    if steer:
        local = jnp.arange(scfg.n_routed_experts) - scfg.first_expert
        p["router"] = {"kernel": p["router"]["kernel"].at[0].set(jnp.where((local >= 0) & (local < 2), 50.0, -50.0))}
        u = u.at[:, 0].set(1.0)
    return p, u, scfg


@pytest.mark.parametrize("routing", ["sigmoid", "softmax"])
@pytest.mark.parametrize("steer", [False, True], ids=["load_inside_the_buffer", "load_over_the_buffer"])
def test_the_load_sized_buffer_gives_the_worst_cases_values_and_gradients_with_no_shared_expert(small, seeded, routing, steer):
    """The update's form of the expert layer (`moe(fit=True)`) against the worst case's: values, and the gradients of
    the input, the router and the three expert kernels; a load over the buffer takes the worst case's, and no pair
    is dropped."""
    module = small[1]
    p, u, scfg = two_held(seeded["layer_1"]["moe"], module.cfg, routing, steer)
    assert (scfg.load_rows(FIT_TOKENS), scfg.expert_rows(FIT_TOKENS)) == (256, 512)

    def run(fit):
        def loss(p, u):
            y, load = seq.moe(p, u, scfg, fit=fit)
            return jnp.sum(jnp.sin(y)), (y, load)

        (_, (y, load)), (gp, gu) = jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(p, u)
        return y, load, [gu, gp["router"]["kernel"], *(k["kernel"] for k in gp["experts"].values())]

    (y, load, grads), (want, want_load, want_grads) = run(True), run(False)
    close(y, want)
    for g, w in zip(grads, want_grads):
        close(g, w)
    routed = int(want_load["routed_here"])
    assert int(load["routed_here"]) == routed and int(load["dropped"]) == 0 and int(want_load["rows"]) == 512
    fallbacks, rows = int(load["fallbacks"]), int(load["rows"])
    assert (fallbacks, rows) == ((1, 512) if steer else (0, 256)) and (routed == 512 if steer else 0 < routed <= 256)


def test_at_the_fixtures_size_the_updates_expert_layer_has_no_branch(small):
    """4 of 8 experts held and top-2: twice a uniform router's load is every pair that can come, so the update's
    forward lowers with no `cond` (the worst case's program); with 2 of 16 held the `cond` is there."""
    _, module, _, shapes, r = small
    rows = B * (1 + S) * T * L

    def branches(cfg, shapes):
        fwd = lambda p: bd.forward_train(p, r["tokens"], r["is_first"], r["actions"], r["order"], cfg, S, module.mask_id)  # noqa: E731
        tree = nest({n: jax.ShapeDtypeStruct(*sd) for n, sd in shapes.items()})
        return "stablehlo.case" in jax.jit(jax.grad(lambda p: jnp.sum(fwd(p)[0][..., 0]))).lower(tree).as_text()

    two = module.cfg._replace(experts_held=2, n_routed_experts=16)
    two_shapes = {n: ((2, *shape[1:]) if "/experts/" in n else (shape[0], 16) if n.endswith("router/kernel") else shape, dtype)
                  for n, (shape, dtype) in shapes.items()}
    assert module.cfg.load_rows(rows) == module.cfg.expert_rows(rows) and not branches(module.cfg, shapes)
    assert two.load_rows(rows) < two.expert_rows(rows) and branches(two, two_shapes)


def test_every_expert_op_of_the_update_lies_under_moe_and_experts(small):
    """The benchmark books the experts' device time by the `op_name`'s whole components (`span_reduce.part_of`): with 2
    of 16 experts held, every grouped product of the update, in both branches of the `cond` (the worst case's first),
    forward and backward (which computes the branch's forward again), carries both `moe` and `experts`."""
    from perfbench.span_reduce import part_of

    _, module, _, shapes, r = small
    two = module.cfg._replace(experts_held=2, n_routed_experts=16)
    tree = nest({n: jax.ShapeDtypeStruct((2, *shape[1:]) if "/experts/" in n else (shape[0], 16) if n.endswith("router/kernel") else shape, dtype)
                 for n, (shape, dtype) in shapes.items()})
    fwd = lambda p: bd.forward_train(p, r["tokens"], r["is_first"], r["actions"], r["order"], two, S, module.mask_id)  # noqa: E731
    text = jax.jit(jax.grad(lambda p: jnp.sum(fwd(p)[0][..., 0]))).lower(tree).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*ragged_dot_general)"', text))
    assert names and all(part_of(n, ("moe",)) == "moe" and part_of(n, ("experts",)) == "experts" for n in names), names
    branches = {re.search(r"/cond/(branch_\d)_fun/", n).group(1) for n in names}
    passes = {next(p for p in ("transpose", "jvp") if p in n) for n in names}
    assert branches == {"branch_0", "branch_1"} and passes == {"transpose", "jvp"}, names


def test_the_env_step_multiplies_the_worst_cases_rows_with_no_loop_or_branch(small, seeded):
    """The env step's forwards route 2 L and L rows an env and every held expert is chosen: its grouped products keep
    the worst case's buffer, `expert_rows`, outside any loop or branch."""
    from sheeprl_tpu.algos.ppo_recurrent import block_diffusion_policy as bdp

    _, module, _, _, rollout = small
    scfg = module.cfg
    act = bdp.make_act_fn(module)
    jaxpr = jax.make_jaxpr(act)(seeded, bdp.new_state(module, B, T), rollout["tokens"][:, 0], rollout["is_first"][:, 0],
                                jax.random.key(0)).jaxpr

    def eqns(jaxpr, inside=()):
        for eqn in jaxpr.eqns:
            yield eqn, inside
            flow = (eqn.primitive.name,) if eqn.primitive.name in ("while", "cond") else ()
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns(sub, inside + flow)

    products = [(inside, e.invars[0].aval.shape[0]) for e, inside in eqns(jaxpr) if e.primitive.name == "ragged_dot_general"]
    assert len(products) == 3 * S * scfg.num_hidden_layers
    assert {inside for inside, _ in products} == {()} and {rows for _, rows in products} == {scfg.expert_rows(B * 2 * L), scfg.expert_rows(B * L)}


def test_the_config_reads_the_published_keys_and_refuses_what_it_cannot_run(small):
    cfg, module, _, _, _ = small
    scfg = module.cfg
    assert (scfg.attention, scfg.scoring_func, scfg.qk_head_dim, scfg.rope_dim, scfg.n_shared_experts, scfg.hc_mult) == ("gqa", "softmax", 16, 16, 0, 1)
    assert (scfg.rope_factor, scfg.routed_scaling_factor, bd.kv_heads(scfg)) == (1.0, 1.0, 2)
    node = dict(cfg.algo.backbone)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        seq.SequenceConfig.from_node({**node, "norm_topk_prob": False})
    with pytest.raises(ValueError, match="scoring_func"):
        seq.SequenceConfig.from_node({**node, "scoring_func": "relu"})


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """`exp=ppo_recurrent_sdar` at tiny widths, two iterations through `cli.run`: its events."""
    from sheeprl_tpu.cli import run

    cwd = os.getcwd()
    os.chdir(tmp_path_factory.mktemp("bd_run"))
    try:
        run(TINY + ["run_name=bd_tiny", "metric.log_level=1"])
        with open(glob.glob("logs/runs/ppo_recurrent/*/bd_tiny/version_0/telemetry.jsonl")[0]) as f:
            return [json.loads(line) for line in f]
    finally:
        os.chdir(cwd)


def test_the_recipe_trains_through_cli_run_with_its_events(tiny_run):
    from sheeprl_tpu.telemetry.schema import SPAN_SCHEMAS, validate_event

    events = tiny_run
    named = [e for e in events if e["event"] in ("moe_load", "sequence_policy", "block_diffusion")]
    assert [validate_event(e) for e in named] == [[]] * 4
    blocks = next(e for e in events if e["event"] == "block_diffusion")
    # 3 envs x 4 ids / 2 steps a forward; one minibatch of 3 sequences x 4 blocks x 4 ids x (1 clean + 2 copies)
    assert [blocks[k] for k in ("block_length", "denoising_steps", "tokens_per_forward", "update_tokens", "mask_id")] == [4, 2, 6, 144, 96]
    assert blocks["train_attention"] == "xla" and "attn_blocks_computed" not in blocks  # the kernel runs on a TPU alone
    share = next(e for e in events if e["event"] == "sequence_policy")
    assert share["cache_layout"] == {"k": [0, 1, 2, 3, 4], "v": [0, 1, 2, 3, 4]} and share["cache_bytes"] == 2 * 2 * 3 * 16 * 32 * 4 + 4 + 3 * 4
    loads = [e for e in events if e["event"] == "moe_load"]
    # 2 layers x 1 epoch x 1 minibatch of 144 rows, a slot for each of a row's 2 choices
    assert len(loads) == 2 and all(e["dropped"] == 0 and e["rows"] == 2 * 144 * 2 for e in loads)
    spans = set().union(*(e["spans"] for e in events if e["event"] in ("log", "shutdown")))
    assert {"Time/cache_reset", "Player/act", "Player/record", "Time/train_time"} <= spans <= set(SPAN_SCHEMAS)
    logs = [e for e in events if e["event"] == "log"]
    assert logs and all(np.isfinite(e["metrics"]["Loss/policy_loss"]) for e in logs)
