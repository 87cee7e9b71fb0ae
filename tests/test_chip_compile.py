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


# -- the device replay ring: the recipe's buffer and the benchmark's two cells ----
# rows x envs x Discrete(n): the leaves are `rgb u8[64,64,3]`, the action's
# one-hot and the four float scalars, in the shapes the prefetcher allocates
RINGS = {"recipe": (RECIPE_ROWS, 1, N_ACT), "dv3_xl.crafter": (220_000, 1, 17), "dv3_l.navigate4": (75_000, 4, 10)}


def _ring(one_chip, size):
    """(ring leaves as stored, the gathers' `items`, the ring's bytes by
    rows x row bytes)."""
    from sheeprl_tpu.data.device_ring import stored_item_shape

    rows, n_envs, n_act = RINGS[size]
    items = {"rgb": ((64, 64, 3), jnp.uint8), "actions": ((n_act,), jnp.float32)}
    items.update({k: ((1,), jnp.float32) for k in ("rewards", "terminated", "truncated", "is_first")})
    ring = {k: _sds(one_chip, (rows, n_envs) + stored_item_shape(item, dt), dt) for k, (item, dt) in items.items()}
    assert ring["rgb"].shape == (rows, n_envs, 96, 128)
    restore = tuple((k, item) for k, (item, dt) in items.items() if stored_item_shape(item, dt) != item)
    nbytes = sum(rows * n_envs * int(np.prod(item)) * np.dtype(dt).itemsize for item, dt in items.values())
    return ring, restore, nbytes


def _touches_only_its_rows(mem, ring_bytes):
    """No whole-buffer copy (the logical `u8[rows, envs, 64, 64, 3]` carried a
    temp of twice the ring in every one of these programs: its default layout
    puts the row axis minor-most), no padded layout, and the program's peak
    leaves the train program its room."""
    assert mem.temp_size_in_bytes < 0.10 * ring_bytes, mem
    assert mem.argument_size_in_bytes < 1.01 * ring_bytes, mem
    peak = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes
    assert peak + 2 * 10**9 < HBM_BYTES, mem


@pytest.mark.parametrize("size", list(RINGS))
def test_device_ring_gather_compiles_for_v5e_without_a_whole_buffer_copy(one_chip, size):
    from sheeprl_tpu.data.device_ring import _gather_batch

    ring, restore, nbytes = _ring(one_chip, size)
    lowered = _gather_batch.lower(
        ring, _sds(one_chip, (1, T, B), jnp.int32), _sds(one_chip, (B,), jnp.int32), (), items=restore
    )
    assert lowered.out_info["rgb"].shape == (1, T, B, 64, 64, 3)  # the batch train receives
    _touches_only_its_rows(lowered.compile().memory_analysis(), nbytes)


@pytest.mark.parametrize("size", list(RINGS))
def test_device_ring_scatter_compiles_for_v5e_in_place(one_chip, size):
    from sheeprl_tpu.data.device_ring import _scatter_rows

    ring, _, nbytes = _ring(one_chip, size)
    n = 8 * (1 + 8 * (ring["rgb"].shape[1] > 1))  # 8 padded rows, 72 with four envs
    rows = {k: _sds(one_chip, (n,) + ring[k].shape[2:], ring[k].dtype) for k in ring}
    idx = _sds(one_chip, (n,), jnp.int32)
    mem = _scatter_rows.lower(ring, rows, idx, idx).compile().memory_analysis()
    _touches_only_its_rows(mem, nbytes)
    assert mem.alias_size_in_bytes >= nbytes  # the donated ring is updated in place


@pytest.mark.parametrize("size", list(RINGS))
def test_uniform_ring_gather_compiles_for_v5e_without_a_whole_buffer_copy(one_chip, size):
    from sheeprl_tpu.data.device_ring import _gather_uniform

    ring, restore, nbytes = _ring(one_chip, size)
    idx = _sds(one_chip, (256,), jnp.int32)
    lowered = _gather_uniform.lower(ring, idx, idx, 1, 256, ("rgb",), (), items=restore)
    assert lowered.out_info["next_rgb"].shape == (1, 256, 64, 64, 3)
    _touches_only_its_rows(lowered.compile().memory_analysis(), nbytes)


@pytest.mark.parametrize("size", list(RINGS))
def test_uniform_ring_scatter_compiles_for_v5e_in_place(one_chip, size):
    from sheeprl_tpu.data.device_ring import _scatter_steps

    ring, _, nbytes = _ring(one_chip, size)
    rows = {k: _sds(one_chip, (8,) + ring[k].shape[1:], ring[k].dtype) for k in ring}
    mem = _scatter_steps.lower(ring, rows, _sds(one_chip, (8,), jnp.int32)).compile().memory_analysis()
    _touches_only_its_rows(mem, nbytes)
    assert mem.alias_size_in_bytes >= nbytes


# -- the sequence policy's decode step: the latent cache in the layout it is written in ----
# the cell's MLA widths (kv_lora_rank 512 + rope 64: a minor axis of 576, no multiple of 128), 32 envs, capacity 512;
# the rest cut so that one compile takes seconds
SEQ_CUT = [
    "exp=ppo_recurrent_xing4", "algo.backbone.hidden_size=256", "algo.backbone.num_hidden_layers=2",
    "algo.backbone.first_k_dense_replace=1", "algo.backbone.n_routed_experts=8", "algo.backbone.experts_held=2",
    "algo.backbone.vocab_size=1024", "algo.backbone.vocab_held=512", "algo.backbone.num_attention_heads=4",
    "algo.backbone.intermediate_size=1024", "algo.backbone.moe_intermediate_size=256",
]
SEQ_ENVS, SEQ_CAPACITY = 32, 512


@pytest.fixture(scope="module")
def decode_carry(one_chip):
    """(module, params, the carry on the chip as `main` places it, the cache's bytes)."""
    from sheeprl_tpu.algos.ppo_recurrent import sequence_policy as sp
    from sheeprl_tpu.algos.ppo_recurrent.agent import SequencePolicy
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.models import sequence as seq

    scfg = seq.SequenceConfig.from_node(compose("config", SEQ_CUT).algo.backbone)
    assert (scfg.kv_lora_rank, scfg.qk_rope_head_dim) == (512, 64)
    module = SequencePolicy(scfg, "token")
    params = _like(one_chip, jax.eval_shape(lambda k: seq.init_params(scfg, k), jax.random.key(0)))
    state = _like(one_chip, jax.eval_shape(lambda: sp.new_state(module, SEQ_ENVS, SEQ_CAPACITY)))
    latents = state["cache"]["latents"]
    return module, params, state, int(np.prod(latents.shape)) * latents.dtype.itemsize


def _whole_cache_copies(compiled, carry):
    shape = "f32[%s]" % ",".join(map(str, carry["cache"]["latents"].shape))
    return [line.strip()[:120] for line in compiled.as_text().splitlines() if " copy(" in line and shape + "{" in line]


@pytest.mark.parametrize("program", ["act", "restart", "value_fn"])
def test_decode_step_compiles_for_v5e_with_the_cache_updated_in_place(one_chip, decode_carry, program):
    """The TPU's default layout for `f32[.., 512, 576]` puts the capacity axis minor-most; a step that writes one row
    row-major relaid the whole cache out and back each call (two copies of 189 MB at the cell's 5 layers), and the
    restart copied it once. Held `[.., 512, 640]` (`seq.cache_width`), the default layout is row-major."""
    from sheeprl_tpu.algos.ppo_recurrent import sequence_policy as sp

    module, params, carry, cache_bytes = decode_carry
    tokens = _sds(one_chip, (SEQ_ENVS,), jnp.int32)
    if program == "act":
        key = _like(one_chip, jax.eval_shape(lambda: jax.random.key(0)))
        lowered = sp.make_act_fn(module).lower(params, carry, tokens, _sds(one_chip, (SEQ_ENVS,), jnp.bool_), key)
    elif program == "restart":
        lowered = sp.restart.lower(carry)
    else:  # a truncation's bootstrap reads the cache and returns values alone
        lowered = sp.make_value_fn(module).lower(params, carry, tokens)
    compiled = lowered.compile()
    assert _whole_cache_copies(compiled, carry) == []
    if program != "value_fn":
        assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes  # the donated cache comes back in place
