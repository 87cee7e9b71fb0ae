"""Ask the TPU's compiler, without a TPU: the programs of the main path at
their real sizes, compiled for a described v5e chip.

The compiler refuses here what the chip would refuse: a kernel slice off the
tiling, more scoped VMEM than a kernel may use, a program that does not fit
16 GB of HBM. Nothing runs, so nothing here is a result or a time, and a
compile that passes is not a chip run (`python chip_smoke.py` is).

Code that asks `jax.default_backend()` still sees the CPU in this process, so
the tests steer it through existing options: `conv_impl=xla` (what `auto`
resolves to on a TPU) and `pallas_gru=True` (the compiled kernel).

All in ONE file, and the topology is described inside a module-scoped fixture:
only one process at a time may load the TPU's library, so it must happen in
the one xdist worker that runs this file, after collection, never at import.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 10**9  # one v5e chip
RECIPE_ROWS = 100_000  # exp/dreamer_v3_100k_ms_pacman.yaml buffer.size, one env
RING_RGB_BYTES = RECIPE_ROWS * 64 * 64 * 3  # the uint8 frames of the device ring
T, B = 64, 16  # the recipe's [sequence, batch]
N_ACT = 2  # the dummy env's Discrete(2)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache but cannot be read
    # back without a chip (the next run would warn and compile again)
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _like(one_chip, tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)


# -- the Pallas GRU at the widths the loop hands it ---------------------------
# (F, H): F = recurrent_model.dense_units, H = recurrent_state_size
GRU_SIZES = {"XS": (256, 256), "S": (512, 512)}


def _gru_args(one_chip, size):
    F, H = GRU_SIZES[size]
    return (
        _sds(one_chip, (T, B, F)),  # feats
        _sds(one_chip, (T, B, 1)),  # is_first
        _sds(one_chip, (H,)),  # the learnable initial state, [H] as RSSM passes it
        _sds(one_chip, (F + H, 3 * H)),
        _sds(one_chip, (3 * H,)),
        _sds(one_chip, (3 * H,)),
    )


@pytest.mark.parametrize("size", list(GRU_SIZES))
def test_pallas_gru_forward_compiles_for_v5e(one_chip, size):
    from sheeprl_tpu.ops.pallas_gru import fits_vmem, gru_sequence

    assert fits_vmem(*GRU_SIZES[size])
    compiled = jax.jit(lambda *a: gru_sequence(*a, False)).lower(*_gru_args(one_chip, size)).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, not a scan


@pytest.mark.parametrize("size", list(GRU_SIZES))
def test_pallas_gru_backward_compiles_for_v5e(one_chip, size):
    """The BPTT kernel keeps the weight block AND its gradient resident: at S
    that is 2 x 6.3 MB against the compiler's 16 MB of scoped VMEM."""
    from sheeprl_tpu.ops.pallas_gru import gru_sequence

    def loss(feats, first, h_first, w, scale, bias):
        return jnp.sum(gru_sequence(feats, first, h_first, w, scale, bias, False) ** 2)

    grad = jax.jit(jax.grad(loss, argnums=(0, 2, 3, 4, 5)))
    compiled = grad.lower(*_gru_args(one_chip, size)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2  # forward and backward kernels


# -- the whole DV3-S train program --------------------------------------------
@pytest.mark.parametrize(
    "rssm",
    [
        pytest.param([], id="scan"),  # the recipe as a user runs it
        pytest.param(
            ["algo.world_model.decoupled_rssm=True", "algo.world_model.pallas_gru=True"], id="pallas"
        ),
    ],
)
def test_dv3_s_train_step_compiles_for_v5e_with_native_convs(one_chip, rssm):
    """DreamerV3-S at its published widths, [T 64, B 16], on the native-conv
    path no CPU test compiles (`conv_impl: auto` is the einsum lowering here,
    and with it the observation loss changes form)."""
    import gymnasium as gym

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import build_optimizers, make_train_fn
    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.parallel import Distributed

    cfg = compose(
        "config",
        [
            "exp=dreamer_v3_100k_ms_pacman",
            "env=dummy",
            "env.id=discrete_dummy",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
            "algo.world_model.conv_impl=xla",
        ]
        + rssm,
    )
    assert (int(cfg.algo.per_rank_sequence_length), int(cfg.algo.per_rank_batch_size)) == (T, B)
    assert int(cfg.algo.dense_units) == 512 and int(cfg.buffer.size) == RECIPE_ROWS
    space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8)})
    wm, actor, critic, params = build_agent(
        Distributed(devices=1), cfg, space, [N_ACT], False, jax.random.key(0)
    )
    txs, opt_states = build_optimizers(cfg, params)
    train = make_train_fn(wm, actor, critic, txs, cfg, False, [N_ACT])
    batch = {
        "rgb": _sds(one_chip, (1, T, B, 64, 64, 3), jnp.uint8),
        "actions": _sds(one_chip, (1, T, B, N_ACT)),
        **{k: _sds(one_chip, (1, T, B, 1)) for k in ("rewards", "terminated", "truncated", "is_first")},
    }
    keys = jax.random.split(jax.random.key(1), 1)
    compiled = train.lower(
        *_like(one_chip, (params, opt_states, init_moments())), batch, _like(one_chip, keys)
    ).compile()
    text = compiled.as_text()
    assert " convolution(" in text  # native convs: the program the chip runs
    assert ("tpu_custom_call" in text) == bool(rssm)
    mem = compiled.memory_analysis()
    resident = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
    # beside it the recipe keeps its replay ring on the device (buffer.device_cache: auto)
    assert resident + RING_RGB_BYTES < HBM_BYTES, (mem, RING_RGB_BYTES)


# -- the device replay ring at the recipe's buffer size -------------------------
def _ring(one_chip, rows):
    items = {"rgb": ((64, 64, 3), jnp.uint8), "actions": ((N_ACT,), jnp.float32)}
    items.update({k: ((1,), jnp.float32) for k in ("rewards", "terminated", "truncated", "is_first")})
    return {k: _sds(one_chip, (rows, 1) + shape, dtype) for k, (shape, dtype) in items.items()}, items


def _fits_beside_train_step(mem):
    """No padded layout blew the 1.2 GB of frames up, and the program's peak
    leaves the DV3-S train program (1.6 GB) its room."""
    assert mem.argument_size_in_bytes < 1.1 * RING_RGB_BYTES, mem
    peak = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes
    assert peak + 2 * 10**9 < HBM_BYTES, mem


def test_device_ring_gather_compiles_for_v5e_at_recipe_size(one_chip):
    from sheeprl_tpu.data.device_ring import _gather_batch

    ring, _ = _ring(one_chip, RECIPE_ROWS)
    compiled = _gather_batch.lower(
        ring, _sds(one_chip, (1, T, B), jnp.int32), _sds(one_chip, (B,), jnp.int32), ()
    ).compile()
    _fits_beside_train_step(compiled.memory_analysis())


def test_device_ring_scatter_compiles_for_v5e_at_recipe_size(one_chip):
    from sheeprl_tpu.data.device_ring import _scatter_rows

    ring, items = _ring(one_chip, RECIPE_ROWS)
    rows = {k: _sds(one_chip, (8,) + shape, dtype) for k, (shape, dtype) in items.items()}
    idx = _sds(one_chip, (8,), jnp.int32)
    compiled = _scatter_rows.lower(ring, rows, idx, idx).compile()
    mem = compiled.memory_analysis()
    _fits_beside_train_step(mem)
    assert mem.alias_size_in_bytes >= RING_RGB_BYTES  # the donated ring is updated in place
