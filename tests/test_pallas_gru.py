"""Pallas scan-resident GRU kernel: forward parity with the XLA reference
scan, gradient parity through the custom VJP, and the VMEM-fit guard.
Runs the kernel in interpret mode (no TPU in CI)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.ops.pallas_gru import fits_vmem, gru_sequence, reference_sequence

T, B, F, H = 6, 4, 16, 8


def _inputs(seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    feats = jax.random.normal(k[0], (T, B, F))
    first = jnp.zeros((T, B, 1)).at[0].set(1.0).at[3, 1].set(1.0)
    h_first = jax.random.normal(k[1], (H,)) * 0.5
    w = jax.random.normal(k[2], (F + H, 3 * H)) / np.sqrt(F + H)
    scale = 1.0 + 0.1 * jax.random.normal(k[3], (3 * H,))
    bias = 0.1 * jax.random.normal(k[4], (3 * H,))
    return feats, first, h_first, w, scale, bias


def test_forward_parity_with_reference():
    args = _inputs()
    ref = reference_sequence(*args)
    out = gru_sequence(*args, True)  # interpret mode
    assert out.shape == (T, B, H)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_is_first_resets_are_honored():
    feats, first, h_first, w, scale, bias = _inputs()
    out = gru_sequence(feats, first, h_first, w, scale, bias, True)
    # env 1 resets at t=3: its state there must equal a fresh one-step rollout
    # from h_first, regardless of everything it saw before
    fresh = reference_sequence(
        feats[3:4, 1:2], jnp.ones((1, 1, 1)), h_first, w, scale, bias
    )
    np.testing.assert_allclose(np.asarray(out[3, 1]), np.asarray(fresh[0, 0]), rtol=1e-5, atol=1e-5)


def test_gradient_parity_with_reference():
    args = _inputs(1)

    def loss_kernel(feats, w, scale, bias):
        return jnp.sum(gru_sequence(feats, args[1], args[2], w, scale, bias, True) ** 2)

    def loss_ref(feats, w, scale, bias):
        return jnp.sum(reference_sequence(feats, args[1], args[2], w, scale, bias) ** 2)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2, 3))(args[0], args[3], args[4], args[5])
    gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(args[0], args[3], args[4], args[5])
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_fits_vmem_guard():
    assert fits_vmem(512, 512)  # DreamerV3-S: (1024, 1536) f32 ≈ 6 MB
    assert not fits_vmem(1024, 4096)  # XL: ≈ 250 MB


def test_jit_compiles():
    args = _inputs(2)
    f = jax.jit(lambda *a: gru_sequence(*a, True))
    out = f(*args)
    assert np.isfinite(np.asarray(out)).all()


def test_decoupled_train_paths_agree():
    """The Pallas-GRU decoupled world-model dynamics must match the scan
    path bit-for-bit-ish: same params, same batch, same keys → same losses."""
    from dreamer_tiny import burst_metrics

    base = ["algo.world_model.decoupled_rssm=True"]
    ref = burst_metrics(base)
    pal = burst_metrics(base + ["algo.world_model.pallas_gru=interpret"])
    for k in ("Loss/world_model_loss", "State/kl", "Loss/reward_loss"):
        assert ref[k] == pytest.approx(pal[k], rel=1e-4), (k, ref[k], pal[k])


def test_hfirst_gradient_parity():
    """Reset masks route carry cotangents into h_first; the BPTT kernel must
    accumulate them exactly like the reference VJP (incl. the [H] -> [B, H]
    broadcast reduction)."""
    args = _inputs(3)

    def loss_k(h_first):
        return jnp.sum(gru_sequence(args[0], args[1], h_first, args[3], args[4], args[5], True) ** 2)

    def loss_r(h_first):
        return jnp.sum(reference_sequence(args[0], args[1], h_first, args[3], args[4], args[5]) ** 2)

    gk = jax.grad(loss_k)(args[2])
    gr = jax.grad(loss_r)(args[2])
    assert gk.shape == (H,)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), rtol=1e-4, atol=1e-5)


def test_batched_hfirst_gradient_parity():
    feats, first, _, w, scale, bias = _inputs(4)
    h_first = jax.random.normal(jax.random.key(9), (B, H)) * 0.3

    gk = jax.grad(lambda hf: jnp.sum(gru_sequence(feats, first, hf, w, scale, bias, True) ** 2))(h_first)
    gr = jax.grad(lambda hf: jnp.sum(reference_sequence(feats, first, hf, w, scale, bias) ** 2))(h_first)
    assert gk.shape == (B, H)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize(
    "overrides, match",
    [
        (["algo.world_model.pallas_gru=True"], "decoupled_rssm"),
        (
            ["algo.world_model.decoupled_rssm=True", "algo.world_model.pallas_gru=True",
             "fabric.precision=bf16-mixed"],
            "32-true",
        ),
        (
            ["algo.world_model.decoupled_rssm=True", "algo.world_model.pallas_gru=True",
             "algo.world_model.recurrent_model.recurrent_state_size=4096",
             "algo.world_model.recurrent_model.dense_units=1024"],
            "VMEM",
        ),
        (["algo.world_model.decoupled_rssm=True", "algo.world_model.pallas_gru=maybe"], "interpret"),
    ],
    ids=["coupled", "mixed-precision", "too-wide", "unknown-mode"],
)
def test_pallas_gru_asked_for_where_it_cannot_run_is_an_error(overrides, match):
    """It used to print "UNUSED" and train on the XLA scan."""
    from dreamer_tiny import make_trainer

    with pytest.raises(ValueError, match=match):
        make_trainer(overrides)


def test_pallas_gru_true_means_the_compiled_kernel_not_the_interpreter():
    """`True` no longer turns into interpret mode off a TPU: on this CPU
    backend the compiled kernel cannot be lowered, and the burst says so."""
    from dreamer_tiny import burst_metrics

    with pytest.raises(ValueError, match="[Ii]nterpret"):
        burst_metrics(["algo.world_model.decoupled_rssm=True", "algo.world_model.pallas_gru=True"])
