"""The sequence backbone of the recurrent on-policy loop (`models/sequence.py`,
`algos/ppo_recurrent/sequence_policy.py`) at a small size with every
mechanism on: hidden 64, 2 of 4 heads held, 8 experts with 2 held and top-2,
4 streams with 20 Sinkhorn rounds, 1 dense + 2 expert layers, a vocabulary
slice of 32 of 64. The program against the benchmark's plain reference
(`perfbench/references/ppo_recurrent_sequence.py`) on seeded weights; decode through
the cache against the full forward; the shares of a layer add up to the
whole; the routed layer drops nothing; the recipe through `cli.run`."""
import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.adapters import ppo_recurrent_sequence as adapter
from perfbench.reference import flatten, make_weights, nest
from perfbench.references import ppo_recurrent_sequence as reference
from sheeprl_tpu.models import sequence as seq

TINY = [
    "exp=ppo_recurrent_xing4", "env.num_envs=4", "algo.rollout_steps=16", "algo.per_rank_num_batches=2", "algo.total_steps=128",
    "algo.backbone.hidden_size=64", "algo.backbone.q_lora_rank=24", "algo.backbone.kv_lora_rank=16", "algo.backbone.qk_nope_head_dim=8",
    "algo.backbone.qk_rope_head_dim=8", "algo.backbone.v_head_dim=8", "algo.backbone.num_attention_heads=4",
    "algo.backbone.intermediate_size=96", "algo.backbone.moe_intermediate_size=32", "algo.backbone.n_routed_experts=8",
    "algo.backbone.num_experts_per_tok=2", "algo.backbone.first_k_dense_replace=1", "algo.backbone.num_hidden_layers=3",
    "algo.backbone.vocab_size=64", "algo.backbone.experts_held=2", "algo.backbone.first_expert=2", "algo.backbone.heads_held=2",
    "algo.backbone.vocab_held=32", "env.wrapper.n_steps=6", "checkpoint.save_last=False", "checkpoint.every=1000000",
    "model_manager.disabled=True", "metric.log_every=64", "buffer.memmap=False",
]
B, T, SEED = 3, 12, 3000000019
FEW_ROUNDS = ["algo.backbone.hc_sinkhorn_iters=3"]  # where the rounds are not what is tested: a fifth of the graph to compile


def _sizes(overrides):
    from sheeprl_tpu.config import compose

    cfg = compose("config", TINY + overrides)
    scfg = seq.SequenceConfig.from_node(cfg.algo.backbone)
    sz = adapter.sizes_for(cfg, 1, B)._replace(envs=B, steps=T, epochs=1)
    tree = jax.eval_shape(lambda k: seq.init_params(scfg, k), jax.random.key(0))
    shapes = {n: (tuple(x.shape), x.dtype) for n, x in flatten(tree).items()}
    tokens = jax.random.randint(jax.random.key(2), (B, T), 0, scfg.vocab_held)
    is_first = jnp.zeros((B, T), bool).at[:, 0].set(True).at[1, 5].set(True).at[2, 7].set(True).at[2, 8].set(True)
    return cfg, scfg, sz, shapes, tokens, is_first


@pytest.fixture(scope="module")
def small():
    """(composed config, the program's SequenceConfig, the reference's Sizes, the leaves' shapes, tokens, is_first)."""
    return _sizes([])


@pytest.fixture(scope="module")
def program_forward(small):
    _, scfg, _, shapes, tokens, is_first = small
    return jax.jit(lambda p: seq.forward_train(p, tokens, is_first, scfg, choices=True))(weights(shapes))


def weights(shapes):
    return nest(dict(make_weights(SEED, shapes)))


def minibatch(scfg, tokens, is_first):
    k = jax.random.split(jax.random.key(5), 5)
    f = lambda i: jax.random.normal(k[i], tokens.shape)  # noqa: E731
    return {"tokens": tokens, "is_first": is_first, "actions": jax.random.randint(k[0], tokens.shape, 0, scfg.vocab_held),
            "logprobs": -3.4 + 0.1 * f(1), "values": f(2), "returns": f(3), "advantages": f(4)}


def close(a, b, tol=2e-4):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and np.max(np.abs(a - b)) <= tol * (1.0 + np.max(np.abs(b))), float(np.max(np.abs(a - b)))


def test_the_forward_agrees_with_the_reference_on_seeded_weights(small, program_forward):
    _, scfg, sz, shapes, tokens, is_first = small
    logits, values, load = program_forward
    want_logits, want_values, want_chosen = jax.jit(lambda p: reference.forward(p, tokens, is_first, sz))(weights(shapes))
    close(logits, want_logits)
    close(values, want_values)
    assert np.array_equal(np.asarray(load["chosen"]).reshape(want_chosen.shape), np.asarray(want_chosen))
    assert logits.shape == (B, T, scfg.vocab_held) and int(load["dropped"]) == 0
    assert int(load["rows"]) == 2 * scfg.expert_rows(B * T) and 0 < int(load["routed_here"]) <= int(load["rows"])


def test_losses_gradients_and_one_adam_step_agree_with_the_reference():
    from sheeprl_tpu.algos.ppo_recurrent import sequence_policy as sp
    from sheeprl_tpu.algos.ppo_recurrent.agent import SequencePolicy
    from sheeprl_tpu.config import instantiate
    from sheeprl_tpu.optim import clipped

    cfg, scfg, sz, shapes, tokens, is_first = _sizes(FEW_ROUNDS + ["algo.update_epochs=1"])
    params, mb = weights(shapes), minibatch(scfg, tokens, is_first)
    coefs = {k: jnp.float32(v) for k, v in {"clip_coef": 0.2, "ent_coef": 0.001, "vf_coef": 0.2, "lr_frac": 1.0}.items()}
    (_, want_losses), want_grads = reference._programs(sz, None)["whole"](params, mb, coefs)
    by_layer_losses, by_layer = reference.grads_by_layer(params, mb, coefs, sz)
    for k, g in flatten(want_grads).items():
        close(flatten(by_layer)[k], g, 1e-5)  # a layer at a time is the whole gradient
    assert {k: float(v) for k, v in by_layer_losses.items()} == pytest.approx({k: float(v) for k, v in want_losses.items()}, rel=1e-5)
    # the program's model under the reference's loss: the gradients of the model alone
    got_grads = jax.jit(jax.grad(lambda p: reference.losses_of(*seq.forward_train(p, tokens, is_first, scfg)[:2], mb, coefs, sz)[0]))(params)
    for k, g in flatten(want_grads).items():
        close(flatten(got_grads)[k], g, 1e-3)

    tx = clipped(instantiate(cfg.algo.optimizer), cfg.algo.get("max_grad_norm", 0.0))
    update = sp.make_update_fn(SequencePolicy(scfg, "token"), tx, cfg, 1, B)
    key = jax.random.key(7)
    got_params, _, means, report = update(jax.tree.map(jnp.copy, params), tx.init(params), mb, coefs, key)
    for name, want in want_losses.items():
        assert float(report["losses"][name][0, 0]) == float(means[name]) == pytest.approx(float(want), rel=2e-4, abs=2e-5), name
    after, steps = reference.first_update(dict(make_weights(SEED, shapes)), {k: np.asarray(v) for k, v in mb.items()}, coefs,
                                          np.asarray(jax.random.key_data(key)), sz, whole=True)
    before = make_weights(SEED, shapes)
    for k, v in flatten(got_params).items():
        moved = float(jnp.max(jnp.abs(after[k] - before[k])))
        assert float(jnp.max(jnp.abs(v - after[k]))) <= 2e-3 * moved + 1e-7, k
    assert len(steps) == 1 and float(jnp.max(jnp.abs(flatten(got_params)["layer_1/moe/e_bias"] - before["layer_1/moe/e_bias"]))) == 0.0


def test_decode_through_the_cache_with_resets_equals_the_full_forward(small, program_forward):
    _, scfg, _, shapes, tokens, is_first = small
    params = weights(shapes)
    logits, values, _ = program_forward
    step = jax.jit(lambda p, c, t, f: seq.forward_decode(p, c, t, f, scfg))
    cache, got = seq.new_cache(scfg, B, T), []
    for t in range(T):
        lg, v, cache = step(params, cache, tokens[:, t], is_first[:, t])
        got.append((lg, v))
        if t == 8:  # a token that is not written (a truncation's bootstrap) leaves the cache as it was
            peek = jax.jit(lambda p, c, tok: seq.forward_decode(p, c, tok, jnp.zeros((B,), bool), scfg, write=False))(params, cache, tokens[:, t + 1])
            assert int(peek[2]["pos"]) == t + 1 and np.array_equal(np.asarray(peek[2]["latents"]), np.asarray(cache["latents"]))
    close(jnp.stack([g[0] for g in got], 1), logits)
    close(jnp.stack([g[1] for g in got], 1), values)
    assert int(cache["pos"]) == T and list(np.asarray(cache["start"])) == [0, 5, 8]


def test_the_cache_pad_is_never_read_through_act_restart_and_value_fn(small):
    """The cache is held `[.., capacity, cache_width]`, `kv_lora_rank + rope` rounded up to whole lanes (24 -> 128
    here) so that the TPU holds it row-major. Two rollouts through `act`, `restart` and `value_fn` as the loop drives
    them with the parent's unpadded cache, with zeros in the pad and with NaN there: the same bits, the pad left as it
    was, and the cache handed back in the layout it was given."""
    from sheeprl_tpu.algos.ppo_recurrent import sequence_policy as sp
    from sheeprl_tpu.algos.ppo_recurrent.agent import SequencePolicy

    _, scfg, _, shapes, tokens, is_first = small
    module, params = SequencePolicy(scfg, "token"), weights(shapes)
    act, value_fn, width = sp.make_act_fn(module), sp.make_value_fn(module), scfg.kv_lora_rank + scfg.qk_rope_head_dim
    assert seq.cache_width(scfg) == 128 and seq.cache_width(scfg._replace(kv_lora_rank=512, qk_rope_head_dim=64)) == 640
    got = {}
    for pad in (None, 0.0, np.nan):
        carry, out = sp.new_state(module, B, T), []
        latents = carry["cache"]["latents"]
        carry["cache"]["latents"] = latents[..., :width] if pad is None else latents.at[..., width:].set(pad)
        layout = carry["cache"]["latents"].format.layout.major_to_minor
        for rollout in range(2):
            carry = sp.restart(carry)
            for t in range(T):
                actions, carry = act(params, carry, tokens[:, t], is_first[:, t], jax.random.key(rollout))
                out.append(actions)
                if t == 8:  # a truncation's bootstrap
                    out.append(value_fn(params, carry, tokens[:, t + 1]))
            latents = np.asarray(carry["cache"]["latents"])  # before the next donation
            out += [np.asarray(carry["logprobs"]), np.asarray(carry["values"]), latents[..., :width]]
            if pad is not None:
                assert np.array_equal(latents[..., width:], np.full_like(latents[..., width:], pad), equal_nan=True)
            assert carry["cache"]["latents"].format.layout.major_to_minor == layout
        got[pad] = [np.asarray(x) for x in out]
    unpadded, zeros, nans = got.values()
    assert len(unpadded) == len(zeros) == len(nans) == 2 * (T + 4)
    assert all(a.dtype == b.dtype and a.tobytes() == b.tobytes() == c.tobytes() for a, b, c in zip(unpadded, zeros, nans))


@pytest.mark.parametrize("grouped", [True, False], ids=["grouped", "acting_form"])
def test_the_expert_shares_add_up_to_the_whole_layer(small, grouped):
    _, scfg, sz, shapes, _, _ = small
    p = weights(shapes)["layer_1"]["moe"]
    full = {n: jax.random.normal(jax.random.fold_in(jax.random.key(11), i), (8, *v["kernel"].shape[1:])) * 0.1
            for i, (n, v) in enumerate(p["experts"].items())}
    u = jax.random.normal(jax.random.key(12), (B * T, scfg.hidden_size))
    whole, _ = reference.experts({**p, "experts": {n: {"kernel": k} for n, k in full.items()}}, u[None], sz._replace(experts_held=8, first_expert=0), None)
    shared = seq.gated_mlp(p["shared"], u)
    parts = 0.0
    for first in range(0, 8, 2):
        share = {**p, "experts": {n: {"kernel": k[first: first + 2]} for n, k in full.items()}}
        y, load = seq.moe(share, u, scfg._replace(first_expert=first), grouped=grouped)
        parts = parts + y - shared
        assert int(load["dropped"]) == 0
    close(parts + shared, whole[0])  # the shared expert counted once


def test_the_head_shares_and_the_vocabulary_slices_add_up(small):
    _, scfg, sz, shapes, tokens, is_first = small
    p = weights(shapes)
    u = jax.random.normal(jax.random.key(13), (B, T, scfg.hidden_size))
    mask = reference.episode_mask(is_first)
    a = p["layer_0"]["attn"]
    qk, kv, vd = scfg.qk_head_dim, scfg.qk_nope_head_dim + scfg.v_head_dim, scfg.v_head_dim
    wide = lambda k, n: jax.random.normal(jax.random.key(k), n) * 0.1  # noqa: E731
    full = {**a, "w_uq": {"kernel": wide(1, (scfg.q_lora_rank, 4 * qk))}, "w_ukv": {"kernel": wide(2, (scfg.kv_lora_rank, 4 * kv))},
            "w_o": {"kernel": wide(3, (4 * vd, scfg.hidden_size))}}
    whole = reference.attention(full, u, mask, sz._replace(heads=4), None)
    parts = 0.0
    for h in (0, 2):  # two shares of two heads: W_o's partial outputs are summed (the absent chips' all-reduce)
        share = {**a, "w_uq": {"kernel": full["w_uq"]["kernel"][:, h * qk:(h + 2) * qk]}, "w_ukv": {"kernel": full["w_ukv"]["kernel"][:, h * kv:(h + 2) * kv]},
                 "w_o": {"kernel": full["w_o"]["kernel"][h * vd:(h + 2) * vd]}}
        parts = parts + seq.mla_train(share, u, jnp.arange(T), mask, scfg, seq.yarn_inv_freq(scfg))
    close(parts, whole)
    X = jax.random.normal(jax.random.key(14), (scfg.hc_mult, B, T, scfg.hidden_size))
    kernel = wide(4, (scfg.hidden_size, 64))
    want, _ = reference.head({**p, "head": {"kernel": kernel}}, jnp.moveaxis(X, 0, 2), sz, None)
    slices = [seq._head({**p, "head": {"kernel": kernel[:, v: v + 32]}}, X, scfg)[0] for v in (0, 32)]
    close(jnp.concatenate(slices, -1), want)  # logits concatenated over the vocabulary's slices


def test_h_res_is_doubly_stochastic_and_its_gradient_is_finite_at_the_clamps(small):
    _, scfg, _, shapes, _, _ = small
    hc = weights(shapes)["layer_0"]["attn_hc"]
    X = jax.random.normal(jax.random.key(15), (scfg.hc_mult, 5, scfg.hidden_size))
    _, _, res = jax.jit(lambda h, x: seq.hc_coefficients(h, x, scfg))(hc, X)
    assert res.shape == (4, 4, 5)
    np.testing.assert_allclose(np.asarray(jnp.sum(res, 0)), 1.0, atol=1e-3)  # columns
    np.testing.assert_allclose(np.asarray(jnp.sum(res, 1)), 1.0, atol=1e-3)  # rows
    steep = {**hc, "a_res": jnp.float32(1e4)}  # every entry at a clamp
    grads = jax.jit(jax.grad(lambda h, x: jnp.sum(jnp.square(seq.hc_write(x, x[0], *seq.hc_coefficients(h, x, scfg)[1:]))), argnums=(0, 1)))(steep, X)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
    assert float(jnp.max(jnp.abs(jnp.clip(steep["a_res"] * 1.0, scfg.mhc_h_res_clamp_min, scfg.mhc_h_res_clamp_max)))) == 30.0


@pytest.mark.parametrize("grouped", [True, False], ids=["grouped", "acting_form"])
def test_every_token_routed_to_one_held_expert_is_computed_and_none_is_dropped(small, grouped):
    """In both forms of the layer: grouped by expert (training), and every held expert over every row (acting)."""
    _, scfg, sz, shapes, _, _ = small
    p = weights(shapes)["layer_1"]["moe"]
    p = {**p, "e_bias": jnp.zeros_like(p["e_bias"]).at[scfg.first_expert].set(100.0)}  # every token's first choice
    u = jax.random.normal(jax.random.key(16), (B * T, scfg.hidden_size))
    y, load = seq.moe(p, u, scfg, grouped=grouped, fit=grouped)  # the block-diffusion update's form of the grouped layer
    want, _ = reference.experts(p, u[None], sz, None)
    close(y, want[0])
    # the rows of the buffer taken: at this size the load-sized buffer is the worst case's
    assert scfg.load_rows(B * T) == scfg.expert_rows(B * T)
    rows = scfg.expert_rows(B * T) if grouped else B * T * scfg.experts_held
    assert int(load["dropped"]) == 0 and B * T <= int(load["routed_here"]) <= int(load["rows"]) == rows and int(load["fallbacks"]) == 0
    other, other_load = seq.moe(p, u, scfg, grouped=not grouped)
    close(y, other, 1e-5)
    assert int(other_load["routed_here"]) == int(load["routed_here"])
    assert float(load["max_over_mean"]) >= 1.0


FIT_TOKENS = 256  # rows of the expert layer's own tests of its `fit` form: the load-sized buffer (256) is half the worst case's


@pytest.fixture(scope="module")
def seeded(small):
    """The fixture's seeded weights, made once for the tests below (each making takes seconds)."""
    return weights(small[3])


def routed_layer(p, scfg, routing, steer):
    """The layer `p` under `routing` (`sigmoid`, which chooses by score plus `e_bias`, or `softmax`) and FIT_TOKENS rows of
    input; with `steer`, one input column the router reads as +50 for a held expert and -50 for the others, so that
    every token's choices are the held experts."""
    scfg = scfg._replace(scoring_func=routing)
    p = {n: v for n, v in p.items() if n != "e_bias"}
    if routing == "sigmoid":
        p["e_bias"] = 0.1 * jax.random.normal(jax.random.key(18), (scfg.n_routed_experts,))
    u = jax.random.normal(jax.random.key(17), (FIT_TOKENS, scfg.hidden_size))
    if steer:
        local = jnp.arange(scfg.n_routed_experts) - scfg.first_expert
        p["router"] = {"kernel": p["router"]["kernel"].at[0].set(jnp.where((local >= 0) & (local < scfg.experts_held), 50.0, -50.0))}
        u = u.at[:, 0].set(1.0)
    return p, u, scfg


def load_sized_and_worst_case(p, u, scfg):
    """((output, load, gradients) of the update's form, the same of the worst case's), the gradients those of `u`, the
    router and the three expert kernels."""
    def run(fit):
        def loss(p, u):
            y, load = seq.moe(p, u, scfg, fit=fit)
            return jnp.sum(jnp.sin(y)), (y, load)

        (_, (y, load)), (gp, gu) = jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(p, u)
        return y, load, {"u": gu, "router": gp["router"]["kernel"], **{n: k["kernel"] for n, k in gp["experts"].items()}}

    return run(True), run(False)


@pytest.mark.parametrize("routing", ["sigmoid", "softmax"])
@pytest.mark.parametrize("steer", [False, True], ids=["load_inside_the_buffer", "load_over_the_buffer"])
def test_the_load_sized_buffer_gives_the_worst_cases_values_and_gradients(small, seeded, routing, steer):
    """The layer's `fit` form (the block-diffusion update's) sizes the buffer for about twice a uniform router's load;
    a load over it takes the worst case's buffer. Either way the same pairs, none dropped, and the worst case's values
    and gradients."""
    _, scfg, _, shapes, _, _ = small
    p, u, scfg = routed_layer(seeded["layer_1"]["moe"], scfg, routing, steer)
    assert scfg.load_rows(FIT_TOKENS) == 256 and scfg.expert_rows(FIT_TOKENS) == 512
    (y, load, grads), (want, want_load, want_grads) = load_sized_and_worst_case(p, u, scfg)
    close(y, want)
    for name, g in want_grads.items():
        close(grads[name], g)
    routed = int(want_load["routed_here"])
    assert int(load["routed_here"]) == routed and int(load["dropped"]) == int(want_load["dropped"]) == 0
    assert int(want_load["rows"]) == 512 and int(want_load["fallbacks"]) == 0
    if steer:  # every pair of every token is here: the worst case's buffer
        assert routed == 512 and (int(load["fallbacks"]), int(load["rows"])) == (1, scfg.expert_rows(FIT_TOKENS))
    else:
        assert 0 < routed <= 256 and (int(load["fallbacks"]), int(load["rows"])) == (0, scfg.load_rows(FIT_TOKENS))


def _has_branch(lowered):
    return "stablehlo.case" in lowered.as_text()


def test_where_the_load_sized_buffer_is_the_worst_cases_the_layer_is_the_worst_cases_program(small, seeded):
    """At the fixture's own size the load-sized buffer holds every pair that can come: the update's form lowers to the
    worst case's program, with no `cond`; where it is smaller the `cond` is there."""
    scfg, p = small[1], seeded["layer_1"]["moe"]

    def lowered(tokens, fit):
        u = jax.ShapeDtypeStruct((tokens, scfg.hidden_size), jnp.float32)
        return jax.jit(jax.grad(lambda p, u: jnp.sum(seq.moe(p, u, scfg, fit=fit)[0]), (0, 1))).lower(p, u)

    assert scfg.load_rows(B * T) == scfg.expert_rows(B * T) and scfg.load_rows(FIT_TOKENS) < scfg.expert_rows(FIT_TOKENS)
    assert lowered(B * T, True).as_text() == lowered(B * T, False).as_text() and not _has_branch(lowered(B * T, True))
    assert _has_branch(lowered(FIT_TOKENS, True)) and not _has_branch(lowered(FIT_TOKENS, False))


def test_the_one_token_update_keeps_the_worst_cases_buffer(small, seeded):
    """`forward_train` asks for no `fit`: at 128 tokens, where the load-sized buffer would be half the worst case's,
    its expert layers lower with no `cond` (the reason is in `moe`'s docstring)."""
    scfg = small[1]
    assert scfg.load_rows(4 * 32) < scfg.expert_rows(4 * 32)
    tokens, first = jax.ShapeDtypeStruct((4, 32), jnp.int32), jax.ShapeDtypeStruct((4, 32), jnp.bool_)
    grad = jax.jit(jax.grad(lambda p, t, f: jnp.sum(seq.forward_train(p, t, f, scfg)[0])))
    assert not _has_branch(grad.lower(seeded, tokens, first))


def all_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs it calls."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from all_eqns(sub)


def test_the_decode_step_keeps_the_acting_form_with_no_loop_or_branch(small, seeded):
    """The decode step's experts are the acting form (every held expert over every row): no grouped product, and no
    loop or branch under `moe`."""
    from sheeprl_tpu.algos.ppo_recurrent import sequence_policy as sp
    from sheeprl_tpu.algos.ppo_recurrent.agent import SequencePolicy

    _, scfg, _, _, tokens, is_first = small
    module, params = SequencePolicy(scfg, "token"), seeded
    act = sp.make_act_fn(module)
    jaxpr = jax.make_jaxpr(act)(params, sp.new_state(module, B, T), tokens[:, 0], is_first[:, 0], jax.random.key(0)).jaxpr
    eqns = list(all_eqns(jaxpr))
    assert not [e for e in eqns if e.primitive.name == "ragged_dot_general"]
    assert any("moe" in str(e.source_info.name_stack) for e in eqns)
    assert not [e for e in eqns if e.primitive.name in ("while", "cond") and "moe" in str(e.source_info.name_stack)]


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """`exp=ppo_recurrent_xing4` at tiny widths, two iterations through `cli.run`: (events, the shapes of everything
    the loop stores of a rollout, (name, the span it lay in, its counts) of every span the tracker recorded)."""
    from sheeprl_tpu.algos.ppo_recurrent import sequence_policy as sp
    from sheeprl_tpu.cli import run
    from sheeprl_tpu.telemetry.spans import SpanTracker

    stored, spans = {}, []
    record = SpanTracker.record

    def tapped_record(tracker, name, seconds, counts=None):
        spans.append((name, tracker.current(), dict(counts or {})))  # the span is off its thread's stack: current() is its parent
        return record(tracker, name, seconds, counts)
    make_update_fn, new_state = sp.make_update_fn, sp.new_state

    def tapped(*args, **kwargs):
        update = make_update_fn(*args, **kwargs)

        def recording(params, opt_state, data, coefs, key):
            stored.update({f"data/{k}": tuple(v.shape) for k, v in data.items()})
            return update(params, opt_state, data, coefs, key)

        return recording

    def tapped_state(*args, **kwargs):
        state = new_state(*args, **kwargs)
        stored.update({f"state/{k}": tuple(v.shape) for k, v in flatten(state).items()})
        return state

    cwd = os.getcwd()
    os.chdir(tmp_path_factory.mktemp("seq_run"))
    sp.make_update_fn, sp.new_state, SpanTracker.record = tapped, tapped_state, tapped_record
    try:
        run(TINY + FEW_ROUNDS + ["run_name=seq_tiny"])
        stream = glob.glob("logs/runs/ppo_recurrent/*/seq_tiny/version_0/telemetry.jsonl")[0]
        with open(stream) as f:
            events = [json.loads(line) for line in f]
    finally:
        sp.make_update_fn, sp.new_state, SpanTracker.record = make_update_fn, new_state, record
        os.chdir(cwd)
    return events, stored, spans


def test_the_recipe_trains_through_cli_run_with_every_new_span_and_event(tiny_run):
    from sheeprl_tpu.telemetry.schema import SPAN_SCHEMAS, validate_event

    events, _, recorded = tiny_run
    assert [validate_event(e) for e in events if e["event"] in ("moe_load", "sequence_policy", "placement")] == [[]] * 4
    loads = [e for e in events if e["event"] == "moe_load"]
    assert len(loads) == 2 and all(e["dropped"] == 0 and 0 < e["slot_occupancy"] <= 1 and e["max_over_mean"] >= 1 for e in loads)
    # 2 expert layers x 2 epochs x 2 minibatches of [16, 2] tokens, a slot for each of a token's 2 choices
    assert all(e["rows"] == 2 * 4 * 32 * 2 and e["routed_here"] == round(e["slot_occupancy"] * e["rows"]) for e in loads)
    assert all(e["fallbacks"] == 0 for e in loads)  # a buffer that holds every pair that can come
    share = next(e for e in events if e["event"] == "sequence_policy")
    assert (share["experts_held"], share["first_expert"], share["heads_held"], share["vocab_held"], share["layers"]) == (2, 2, 2, 32, 3)
    assert share["cache_bytes"] == 3 * 4 * 16 * 128 * 4 + 4 + 4 * 4 and share["cache_layout"] == {"latents": [0, 1, 2, 3]}  # 24 columns held in 128
    placement = next(e for e in events if e["event"] == "placement")
    assert placement["same_device"] == 1 and placement["refresh"] == "alias"
    spans = set().union(*(e["spans"] for e in events if e["event"] in ("log", "shutdown")))
    assert {"Time/cache_reset", "Player/act", "Player/env_step", "Player/record", "Time/env_interaction_time", "Time/train_time",
            "Time/param_refresh", "Player/prepare", "Player/dispatch", "Player/fetch", "Player/bootstrap",
            "Time/update_report"} <= spans <= set(SPAN_SCHEMAS)
    assert SPAN_SCHEMAS["Player/act"] == ("tokens", "cache_rows", "forwards") and "tokens" in SPAN_SCHEMAS["Time/train_time"]
    # the act's three children lie in it, once each an act; the bootstrap in the record; the report on its own
    parents = {n: {p for name, p, _ in recorded if name == n} for n in ("Player/prepare", "Player/dispatch", "Player/fetch",
                                                                         "Player/bootstrap", "Time/update_report")}
    assert parents == {"Player/prepare": {"Player/act"}, "Player/dispatch": {"Player/act"}, "Player/fetch": {"Player/act"},
                       "Player/bootstrap": {"Player/record"}, "Time/update_report": {None}}
    acts = sum(name == "Player/act" for name, _, _ in recorded)
    assert acts == 2 * 16 and all(sum(name == n for name, _, _ in recorded) == acts for n in ("Player/prepare", "Player/dispatch", "Player/fetch"))
    counts = {n: [c for name, _, c in recorded if name == n] for n in ("Player/dispatch", "Player/fetch", "Player/bootstrap")}
    assert all(c == {"arrays": 1, "d2h_bytes": 4 * 4} for c in counts["Player/fetch"])  # one int32 id an env
    # every leaf of the parameters, the cache and the key, and the host arrays: 4 ids (int32) and 4 `is_first` (bool)
    assert all(c == counts["Player/dispatch"][0] for c in counts["Player/dispatch"]) and counts["Player/dispatch"][0]["h2d_bytes"] == 4 * 4 + 4
    assert counts["Player/dispatch"][0]["leaves"] > 10
    assert len(counts["Player/bootstrap"]) >= 2 and all(1 <= c["envs"] <= 4 for c in counts["Player/bootstrap"])  # each rollout's last step
    logs = [e for e in events if e["event"] == "log"]
    assert len(logs) == 2 and all(np.isfinite(e["metrics"]["Loss/policy_loss"]) for e in logs)


def test_no_stored_rollout_leaf_has_a_trailing_axis_of_the_vocabulary(tiny_run):
    _, stored, _ = tiny_run
    assert {"data/tokens", "data/actions", "data/logprobs", "data/values", "data/advantages", "state/logprobs", "state/cache/latents"} <= set(stored)
    assert all(shape == (4, 16) for name, shape in stored.items() if name.startswith("data/")), stored
    assert not [name for name, shape in stored.items() if shape and shape[-1] in (32, 64)], stored  # the slice, the vocabulary


def test_the_lstm_recipe_gives_the_same_parameters_to_the_bit_whether_the_mirror_aliases_or_copies(tmp_path, monkeypatch):
    """The LSTM loop is serial as the sequence backbone's is, but says nothing of it (no cell measures it): its mirror
    copies as before. Were it told `in_order`, the mirror would hold the learner's own buffers: two iterations so
    and two with the copying mirror give the same bits."""
    from sheeprl_tpu.algos.ppo_recurrent import ppo_recurrent as loop
    from sheeprl_tpu.cli import run

    args = ["exp=ppo_recurrent", "env=dummy", "env.id=discrete_dummy", "env.num_envs=2", "env.sync_env=True", "env.capture_video=False",
            "algo.rollout_steps=8", "algo.per_rank_sequence_length=4", "algo.per_rank_num_batches=2", "algo.update_epochs=2",
            "algo.total_steps=32", "algo.mlp_keys.encoder=[state]", "algo.cnn_keys.encoder=[]", "algo.run_test=False",
            "checkpoint.save_last=False", "checkpoint.every=1000000", "model_manager.disabled=True", "buffer.memmap=False", "metric.log_level=0"]
    kept, how = {}, {}
    make_update_fn, make_param_mirror = loop.make_update_fn, loop.make_param_mirror

    def tapped(*a, **k):
        update = make_update_fn(*a, **k)

        def recording(*ua):
            out = update(*ua)
            kept[mode] = jax.tree.map(np.asarray, out[0])
            return out

        return recording

    def mirror_of(*a, **k):
        assert "in_order" not in k  # the loop as it is copies
        made = make_param_mirror(*a, **{**k, "in_order": mode == "alias"})
        how[mode] = made[0].placement["refresh"]
        return made

    monkeypatch.setattr(loop, "make_update_fn", tapped)
    monkeypatch.setattr(loop, "make_param_mirror", mirror_of)
    monkeypatch.chdir(tmp_path)
    for mode in ("alias", "copy"):
        run(args + [f"run_name=lstm_{mode}"])
    assert how == {"alias": "alias", "copy": "copy"}
    a, b = jax.tree.leaves(kept["alias"]), jax.tree.leaves(kept["copy"])
    assert len(a) == len(b) > 10 and all(x.tobytes() == y.tobytes() for x, y in zip(a, b))
