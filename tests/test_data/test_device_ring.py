"""DeviceRingPrefetcher: HBM replay mirror parity with the host buffer.

Runs on the CPU backend (conftest forces an 8-device virtual mesh); the ring
device is cpu:0, which exercises the full scatter/gather path — device
placement is orthogonal to the index math under test.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from sheeprl_tpu.data import EnvIndependentReplayBuffer, SequentialReplayBuffer
from sheeprl_tpu.data.device_ring import (
    DeviceRingPrefetcher,
    as_logical,
    as_stored,
    estimate_row_bytes,
    stored_item_shape,
)

KEYS = ("rgb", "state")


def _row(t, env, n_envs):
    """Deterministic, row-unique content: rgb uint8, state f32."""
    rgb = np.full((1, n_envs, 4, 4, 3), (7 * t + env) % 251, np.uint8)
    state = np.full((1, n_envs, 3), 1000.0 * t + env, np.float32)
    return {
        "rgb": rgb,
        "state": state,
        "actions": np.full((1, n_envs, 2), t, np.float32),
        "rewards": np.full((1, n_envs, 1), t * 0.5, np.float32),
        "terminated": np.zeros((1, n_envs, 1), np.float32),
        "truncated": np.zeros((1, n_envs, 1), np.float32),
        "is_first": np.zeros((1, n_envs, 1), np.float32),
    }


def _make(size=32, n_envs=2):
    rb = EnvIndependentReplayBuffer(
        size, n_envs=n_envs, obs_keys=KEYS, buffer_cls=SequentialReplayBuffer
    )
    ring = DeviceRingPrefetcher(rb, batch_size=4, sequence_length=5, cnn_keys=("rgb",), bucket=8)
    return rb, ring

def _ring_host(ring, host):
    """The ring's leaves on the host in their items' own shapes, whatever
    shape the ring stores them in; ``host[k]`` is ``[size, n_envs, *item]``."""
    return {k: as_logical(np.asarray(v), host[k].shape[2:]) for k, v in ring.ring.items()}


def _host_window(rb, env, start, L, key):
    size = rb.buffer_size
    idx = (start + np.arange(L)) % size
    return rb.buffer[env][key][idx, 0]


def test_gather_matches_host_rows():
    rb, ring = _make()
    for t in range(12):
        rb.add(_row(t, 0, 2))
    batch = ring.take(3)
    t_idx, env_order = ring._last_idx
    assert batch["rgb"].shape == (3, 5, 4, 4, 4, 3)
    assert batch["rgb"].dtype == np.uint8  # cnn keys keep their dtype
    assert batch["state"].dtype == np.float32
    got = np.asarray(batch["state"])
    for g in range(3):
        for b in range(4):
            e = int(env_order[b])
            expect = rb.buffer[e]["state"][t_idx[g, :, b], 0]
            np.testing.assert_array_equal(got[g, :, b], expect)


def test_wraparound_parity():
    rb, ring = _make(size=16)
    # sync incrementally while wrapping the ring twice over
    for t in range(40):
        rb.add(_row(t, 0, 2))
        if t % 7 == 0:
            ring.sync()
    ring.sync()
    ring_host = _ring_host(ring, rb.buffer[0])
    for e in range(2):
        np.testing.assert_array_equal(ring_host["rgb"][:, e], rb.buffer[e]["rgb"][:, 0])
        np.testing.assert_array_equal(ring_host["state"][:, e], rb.buffer[e]["state"][:, 0])


def test_backlog_exceeding_capacity_resyncs_fully():
    """If more rows land between syncs than the ring holds, the circular
    delta would alias — the ring must re-ship the whole stored window."""
    rb, ring = _make(size=16)
    rb.add(_row(0, 0, 2))
    ring.sync()
    for t in range(1, 40):  # 39 new rows ≫ 16 slots, no intermediate sync
        rb.add(_row(t, 0, 2))
    ring.sync()
    ring_host = _ring_host(ring, rb.buffer[0])
    for e in range(2):
        np.testing.assert_array_equal(ring_host["state"][:, e], rb.buffer[e]["state"][:, 0])


def test_per_env_divergent_adds():
    """Done-env closing rows make sub-buffer positions diverge (the
    EnvIndependentReplayBuffer.add(indices) path)."""
    rb, ring = _make(size=16)
    for t in range(6):
        rb.add(_row(t, 0, 2))
    # env 1 gets two extra rows
    extra = {k: v[:, :1] for k, v in _row(99, 1, 2).items()}
    rb.add(extra, indices=[1])
    rb.add(extra, indices=[1])
    ring.sync()
    ring_host = _ring_host(ring, rb.buffer[0])
    assert rb.buffer[0]._pos == 6 and rb.buffer[1]._pos == 8
    for e in range(2):
        pos = rb.buffer[e]._pos
        np.testing.assert_array_equal(
            ring_host["state"][:pos, e], rb.buffer[e]["state"][:pos, 0]
        )


def test_inplace_edit_reshipped():
    """mark_restart rewrites the newest row after it was mirrored; the next
    sync re-ships it (previous-newest-row insurance)."""
    rb, ring = _make(size=16)
    for t in range(5):
        rb.add(_row(t, 0, 2))
    ring.sync()
    rb.mark_restart(1)  # edits env 1's newest row in place
    ring.sync()
    ring_host = _ring_host(ring, rb.buffer[0])["truncated"]
    assert ring_host[4, 1, 0] == 1.0
    assert ring_host[4, 0, 0] == 0.0


def test_stage_take_contract():
    rb, ring = _make()
    for t in range(10):
        rb.add(_row(t, 0, 2))
    ring.stage(2)
    batch = ring.take(2)
    assert batch["rgb"].shape[0] == 2
    # g mismatch falls back to a fresh gather
    ring.stage(1)
    batch = ring.take(3)
    assert batch["rgb"].shape[0] == 3
    # g<=0 stages nothing
    ring.stage(0)
    assert ring._staged is None


def test_insufficient_data_stages_none():
    rb, ring = _make()
    rb.add(_row(0, 0, 2))  # 1 row < sequence_length
    ring.stage(1)
    assert ring._staged is None


def test_resync_after_checkpoint_roundtrip():
    rb, ring = _make(size=16)
    for t in range(9):
        rb.add(_row(t, 0, 2))
    ring.sync()
    state = rb.state_dict()
    rb2 = EnvIndependentReplayBuffer(
        16, n_envs=2, obs_keys=KEYS, buffer_cls=SequentialReplayBuffer
    )
    rb2.load_state_dict(state)
    ring2 = DeviceRingPrefetcher(rb2, 4, 5, cnn_keys=("rgb",))
    ring2.sync()
    for e in range(2):
        np.testing.assert_array_equal(
            _ring_host(ring2, rb2.buffer[0])["state"][:9, e], rb.buffer[e]["state"][:9, 0]
        )


def test_estimate_row_bytes():
    import gymnasium as gym

    space = gym.spaces.Dict(
        {
            "rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8),
            "state": gym.spaces.Box(-1, 1, (7,), np.float32),
        }
    )
    assert estimate_row_bytes(space, act_dim=9) == 64 * 64 * 3 + 7 * 4 + 9 * 4 + 16


def test_rejects_non_sequential_subbuffers():
    from sheeprl_tpu.data import ReplayBuffer

    rb = EnvIndependentReplayBuffer(8, n_envs=1, obs_keys=KEYS, buffer_cls=ReplayBuffer)
    with pytest.raises(TypeError):
        DeviceRingPrefetcher(rb, 2, 2)


# -- uniform ([G, B, ...]) ring: the SAC-family path -----------------------

def _uniform_make(size=32, n_envs=2, batch=4, **kw):
    from sheeprl_tpu.data import ReplayBuffer
    from sheeprl_tpu.data.device_ring import DeviceUniformRingPrefetcher

    rb = ReplayBuffer(size, n_envs=n_envs, obs_keys=KEYS)
    ring = DeviceUniformRingPrefetcher(rb, batch, cnn_keys=("rgb",), bucket=8, **kw)
    return rb, ring


def test_uniform_gather_matches_host():
    rb, ring = _uniform_make()
    for t in range(12):
        rb.add(_row(t, 0, 2))
    batch = ring.take(3)
    idxs, env_idxs = ring._last_idx
    assert batch["state"].shape == (3, 4, 3)
    got = np.asarray(batch["state"]).reshape(12, 3)
    expect = rb["state"][idxs, env_idxs]
    np.testing.assert_array_equal(got, expect)
    assert batch["rgb"].dtype == np.uint8


def test_uniform_next_obs_parity():
    rb, ring = _uniform_make(sample_next_obs=True)
    for t in range(12):
        rb.add(_row(t, 0, 2))
    batch = ring.take(2)
    idxs, env_idxs = ring._last_idx
    assert "next_state" in batch and "next_rgb" in batch
    got = np.asarray(batch["next_state"]).reshape(8, 3)
    expect = rb["state"][(idxs + 1) % rb.buffer_size, env_idxs]
    np.testing.assert_array_equal(got, expect)
    # next_<cnn key> keeps its stored dtype
    assert batch["next_rgb"].dtype == np.uint8


def test_forced_ring_multidevice_policy():
    """Both replay paths shard over dp now; _use_ring still raises for any
    caller that does NOT declare multi-device support (multi_ok=False)."""
    from sheeprl_tpu.data.device_ring import _use_ring

    class _Cfg:
        def select(self, path, default=None):
            return {"buffer.device_cache": "true"}.get(path, default)

    class _Dist:
        world_size = 2
        local_device = None

    with pytest.raises(ValueError, match="single-device on this replay path"):
        _use_ring(_Cfg(), _Dist(), 100, 10)
    assert _use_ring(_Cfg(), _Dist(), 100, 10, multi_ok=True)


def test_uniform_wraparound_and_backlog():
    rb, ring = _uniform_make(size=16)
    rb.add(_row(0, 0, 2))
    ring.sync()
    for t in range(1, 40):
        rb.add(_row(t, 0, 2))
    ring.sync()
    ring_host = _ring_host(ring, rb)
    np.testing.assert_array_equal(ring_host["state"], rb["state"])
    np.testing.assert_array_equal(ring_host["rgb"], rb["rgb"])


# -- dp-sharded ring (multi-device meshes, VERDICT r4 #3) ---------------------


def _sharded_make(n_devices=2, n_envs=4, batch=4, size=32):
    from sheeprl_tpu.data.device_ring import ShardedDeviceRingPrefetcher
    from sheeprl_tpu.parallel import Distributed

    dist = Distributed(devices=n_devices)
    rb = EnvIndependentReplayBuffer(
        size, n_envs=n_envs, obs_keys=KEYS, buffer_cls=SequentialReplayBuffer, seed=3
    )
    ring = ShardedDeviceRingPrefetcher(
        rb, batch_size=batch, sequence_length=5, cnn_keys=("rgb",), dist=dist
    )
    return rb, ring, dist


def _row_per_env(t, n_envs):
    """Row whose content encodes (t, env) per COLUMN: state = 1000*t + env."""
    row = _row(t, 0, n_envs)
    row["state"] = (
        1000.0 * t + np.arange(n_envs, dtype=np.float32)[None, :, None] * np.ones((1, n_envs, 3), np.float32)
    ).astype(np.float32)
    row["rgb"] = (
        (7 * t + np.arange(n_envs, dtype=np.uint8)[None, :, None, None, None]) % 251
        * np.ones((1, n_envs, 4, 4, 3), np.uint8)
    ).astype(np.uint8)
    return row


def test_sharded_gather_matches_host_rows():
    """Each batch column must be a true window of the env sub-buffer the
    owning device mirrors — bit-identical to the host arrays."""
    rb, ring, dist = _sharded_make()
    for t in range(20):
        rb.add(_row_per_env(t, 4))
    batch = ring.take(2)
    assert batch["rgb"].shape[:3] == (2, 5, 4)
    # batches land dp-sharded over the batch axis with no collectives
    assert batch["rgb"].sharding.spec == jax.sharding.PartitionSpec(None, None, "dp")
    # column c of gather g: env + window start recoverable from the content
    host = np.asarray(batch["state"])  # state = 1000*t + env
    for g in range(2):
        for c in range(4):
            env = int(host[g, 0, c, 0] % 1000)
            # device d owns envs [d*2, d*2+2): column c belongs to device c//2
            assert env // 2 == c // 2, (env, c)
            t0 = int(host[g, 0, c, 0] // 1000)
            expect = _host_window(rb, env, t0, 5, "state")
            np.testing.assert_array_equal(np.asarray(batch["state"])[g, :, c], expect)
            np.testing.assert_array_equal(
                np.asarray(batch["rgb"])[g, :, c], _host_window(rb, env, t0, 5, "rgb")
            )


def test_sharded_incremental_sync_and_f32_casts():
    rb, ring, dist = _sharded_make()
    for t in range(12):
        rb.add(_row_per_env(t, 4))
    b1 = ring.take(1)
    assert b1["rewards"].dtype == np.float32
    assert b1["rgb"].dtype == np.uint8  # images stay uint8 in HBM and batch
    for t in range(12, 30):  # wrap around
        rb.add(_row_per_env(t, 4))
    b2 = ring.take(1)
    host = np.asarray(b2["state"])
    for c in range(4):
        t0 = int(host[0, 0, c, 0] // 1000)
        env = int(host[0, 0, c, 0] % 1000)
        np.testing.assert_array_equal(host[0, :, c], _host_window(rb, env, t0, 5, "state"))


def test_sharded_requires_divisible_sizes():
    from sheeprl_tpu.data.device_ring import ShardedDeviceRingPrefetcher
    from sheeprl_tpu.parallel import Distributed

    dist = Distributed(devices=2)
    rb = EnvIndependentReplayBuffer(
        16, n_envs=3, obs_keys=KEYS, buffer_cls=SequentialReplayBuffer
    )
    with pytest.raises(ValueError, match="divisible"):
        ShardedDeviceRingPrefetcher(rb, 4, 2, dist=dist)


def test_sharded_uniform_gather_matches_host():
    """SAC-family twin: per-device env blocks, [G, B] batches pre-sharded
    P(None, 'dp'), content bit-identical to the host arrays."""
    from sheeprl_tpu.data import ReplayBuffer
    from sheeprl_tpu.data.device_ring import ShardedDeviceUniformRingPrefetcher
    from sheeprl_tpu.parallel import Distributed

    dist = Distributed(devices=2)
    rb = ReplayBuffer(32, n_envs=4, obs_keys=KEYS, seed=5)
    for t in range(20):
        rb.add(_row_per_env(t, 4))
    ring = ShardedDeviceUniformRingPrefetcher(
        rb, 8, cnn_keys=("rgb",), sample_next_obs=True, dist=dist
    )
    batch = ring.take(2)
    assert batch["state"].shape == (2, 8, 3)
    assert batch["state"].sharding.spec == jax.sharding.PartitionSpec(None, "dp")
    assert "next_state" in batch and batch["rgb"].dtype == np.uint8
    host = np.asarray(batch["state"])  # state = 1000*t + env
    for g in range(2):
        for b in range(8):
            t = int(host[g, b, 0] // 1000)
            env = int(host[g, b, 0] % 1000)
            # device d owns envs [2d, 2d+2): column b belongs to device b//4
            assert env // 2 == b // 4, (env, b)
            np.testing.assert_array_equal(host[g, b], rb["state"][t, env])
            np.testing.assert_array_equal(
                np.asarray(batch["next_state"])[g, b], rb["state"][(t + 1) % 32, env]
            )


def test_sharded_uniform_requires_divisible_sizes():
    from sheeprl_tpu.data import ReplayBuffer
    from sheeprl_tpu.data.device_ring import ShardedDeviceUniformRingPrefetcher
    from sheeprl_tpu.parallel import Distributed

    dist = Distributed(devices=2)
    rb = ReplayBuffer(16, n_envs=3, obs_keys=KEYS)
    with pytest.raises(ValueError, match="divisible"):
        ShardedDeviceUniformRingPrefetcher(rb, 4, dist=dist)


# -- how a row's item is stored (PR 33): whole native tiles go row-contiguous ---

STORED = [
    # (logical item, dtype, stored item)
    ((64, 64, 3), np.uint8, (96, 128)),  # 12288 = 3 tiles of 4096 uint8
    ((64, 64, 1), np.uint8, (32, 128)),  # exactly one tile
    ((84, 84, 3), np.uint8, (84, 84, 3)),  # 21168: no whole number of tiles
    ((17,), np.float32, (17,)),
    ((1,), np.float32, (1,)),
]


@pytest.mark.parametrize("item,dtype,stored", STORED, ids=[f"{np.dtype(d).name}{list(i)}" for i, d, _ in STORED])
def test_stored_item_shape_round_trip(item, dtype, stored):
    """The rule reads shape and dtype alone, keeps every byte in place, and
    its two views are each other's inverse on numpy and on jax arrays, under
    any leading axes."""
    assert stored_item_shape(item, dtype) == stored
    assert int(np.prod(stored)) == int(np.prod(item))
    rng = np.random.default_rng(0)
    for lead in ((5, 2), (3,), (2, 4, 3)):
        x = (rng.random(lead + item) * 200).astype(dtype)
        kept = as_stored(x, item)
        assert kept.shape == lead + stored
        assert np.shares_memory(kept, x)  # a view: nothing is copied on the host
        np.testing.assert_array_equal(kept.reshape(-1), x.reshape(-1))  # row-major bytes stay where they were
        np.testing.assert_array_equal(as_logical(kept, item), x)
        back = as_logical(as_stored(jax.numpy.asarray(x), item), item)
        assert back.shape == x.shape and back.dtype == x.dtype
        np.testing.assert_array_equal(np.asarray(back), x)


def test_stored_item_shape_counts_tiles_by_dtype():
    assert stored_item_shape((1024,), np.float32) == (8, 128)  # one tile of 32-bit values
    assert stored_item_shape((1024,), np.uint8) == (1024,)  # a quarter of a uint8 tile
    assert stored_item_shape((32, 64), np.float64) == (16, 128)
    assert stored_item_shape((0, 128), np.uint8) == (0, 128)  # nothing to store stays as it is


# "big" fills one uint8 tile and is stored [32, 128]; "rgb" (4x4x3) is stored as it is
IMG_KEYS = ("big", "rgb")


def _img_row(t, n_envs):
    """A row whose image bytes are unique to (t, env), in both image keys."""
    row = _row(t, 0, n_envs)
    row["state"] = (1000.0 * t + np.arange(n_envs, dtype=np.float32))[None, :, None] * np.ones((1, 1, 3), np.float32)
    for key, item in (("big", (64, 64, 1)), ("rgb", (4, 4, 3))):
        row[key] = np.stack(
            [np.random.default_rng(1000 * t + e).integers(0, 256, item, np.uint8) for e in range(n_envs)]
        )[None]
    return row


def _img_make(size=16, n_envs=2):
    rb = EnvIndependentReplayBuffer(
        size, n_envs=n_envs, obs_keys=KEYS + ("big",), buffer_cls=SequentialReplayBuffer, seed=7
    )
    return rb, DeviceRingPrefetcher(rb, batch_size=4, sequence_length=5, cnn_keys=IMG_KEYS, bucket=8)


def _assert_batch_is_host_rows(rb, ring, batch, g):
    """Every column of the batch is the host buffer's rows, byte for byte, in
    the item's own shape and dtype."""
    t_idx, env_order = ring._last_idx
    for key in IMG_KEYS:
        item = rb.buffer[0][key].shape[2:]
        got = np.asarray(batch[key])
        assert got.shape == (g, 5, len(env_order)) + item and got.dtype == np.uint8
        for i in range(g):
            for b, e in enumerate(env_order):
                want = rb.buffer[int(e)][key][t_idx[i, :, b], 0]
                assert got[i, :, b].tobytes() == want.tobytes(), (key, i, b)


def _assert_ring_is_host(rb, ring):
    host = _ring_host(ring, rb.buffer[0])
    for e, b in enumerate(rb.buffer):
        for key in IMG_KEYS + ("state", "truncated"):
            np.testing.assert_array_equal(host[key][:, e], b[key][:, 0])


def test_ring_stores_whole_tiles_contiguous_and_the_rest_as_it_is():
    rb, ring = _img_make()
    rb.add(_img_row(0, 2))
    ring.sync()
    assert ring.ring["big"].shape == (16, 2, 32, 128) and ring.ring["big"].dtype == np.uint8
    assert ring.ring["rgb"].shape == (16, 2, 4, 4, 3)
    assert ring.ring["state"].shape == (16, 2, 3)


@pytest.mark.parametrize("scenario", ["wrap", "mark_dirty", "resync"])
def test_gathered_images_are_host_rows_byte_for_byte(scenario):
    """A key stored row-contiguous and one stored as it is, across a wrap of
    the ring, after a host edit re-shipped through `mark_dirty`, and after
    `resync()` rebuilt the mirror."""
    rb, ring = _img_make(size=16)
    for t in range(40):  # wraps twice, synced in uneven steps
        rb.add(_img_row(t, 2))
        if t % 7 == 0:
            ring.sync()
    if scenario == "mark_dirty":
        ring.sync()
        for key in IMG_KEYS:
            rb.buffer[1][key][3, 0] = 255 - rb.buffer[1][key][3, 0]
        ring.mark_dirty(1, 3)
    elif scenario == "resync":
        ring.sync()
        ring.resync()
        assert ring.ring is None
    batch = ring.take(3)
    _assert_batch_is_host_rows(rb, ring, batch, 3)
    _assert_ring_is_host(rb, ring)


def test_sharded_ring_gathers_contiguous_images_byte_for_byte():
    from sheeprl_tpu.data.device_ring import ShardedDeviceRingPrefetcher
    from sheeprl_tpu.parallel import Distributed

    dist = Distributed(devices=2)
    rb = EnvIndependentReplayBuffer(
        16, n_envs=4, obs_keys=KEYS + ("big",), buffer_cls=SequentialReplayBuffer, seed=3
    )
    ring = ShardedDeviceRingPrefetcher(rb, batch_size=4, sequence_length=5, cnn_keys=IMG_KEYS, dist=dist)
    for t in range(23):  # wraps
        rb.add(_img_row(t, 4))
    batch = ring.take(2)
    assert batch["big"].shape == (2, 5, 4, 64, 64, 1)
    assert batch["big"].sharding.spec == jax.sharding.PartitionSpec(None, None, "dp")
    assert all(r["big"].shape == (16, 2, 32, 128) for r in ring.ring)
    host = np.asarray(batch["state"])  # state = 1000*t + env names the row
    for g in range(2):
        for c in range(4):
            t0, env = int(host[g, 0, c, 0] // 1000), int(host[g, 0, c, 0] % 1000)
            for key in IMG_KEYS:
                want = _host_window(rb, env, t0 % 16, 5, key)
                assert np.asarray(batch[key])[g, :, c].tobytes() == want.tobytes(), (key, g, c)


@pytest.mark.parametrize("next_obs", [False, True], ids=["obs", "next_obs"])
def test_uniform_ring_gathers_contiguous_images_byte_for_byte(next_obs):
    from sheeprl_tpu.data import ReplayBuffer
    from sheeprl_tpu.data.device_ring import DeviceUniformRingPrefetcher

    rb = ReplayBuffer(16, n_envs=2, obs_keys=KEYS + ("big",), seed=11)
    ring = DeviceUniformRingPrefetcher(rb, 4, cnn_keys=IMG_KEYS, sample_next_obs=next_obs, bucket=8)
    for t in range(40):  # wraps twice
        rb.add(_img_row(t, 2))
        if t % 7 == 0:
            ring.sync()
    batch = ring.take(3)
    idxs, env_idxs = ring._last_idx
    assert ring.ring["big"].shape == (16, 2, 32, 128) and ring.ring["rgb"].shape == (16, 2, 4, 4, 3)
    for key in IMG_KEYS:
        item = rb[key].shape[2:]
        got = np.asarray(batch[key])
        assert got.shape == (3, 4) + item and got.dtype == np.uint8
        assert got.tobytes() == rb[key][idxs, env_idxs].tobytes()
        if next_obs:
            nxt = np.asarray(batch[f"next_{key}"])
            assert nxt.shape == (3, 4) + item and nxt.dtype == np.uint8
            assert nxt.tobytes() == rb[key][(idxs + 1) % 16, env_idxs].tobytes()
    host = _ring_host(ring, rb)
    for key in IMG_KEYS + ("state",):
        np.testing.assert_array_equal(host[key], rb[key])


def test_ring_programs_take_rows_in_the_items_own_shape_too():
    """The benchmark's rehearsal lowers both programs with leaves of the
    item's own shape and no `items`: they still compile and behave as before
    this layout, and a stored ring takes rows of either shape."""
    import jax.numpy as jnp

    from sheeprl_tpu.data.device_ring import _gather_batch, _scatter_rows

    rows = np.random.default_rng(0).integers(0, 256, (8, 64, 64, 1), np.uint8)
    idx = jnp.arange(8, dtype=jnp.int32)
    e_idx = jnp.zeros((8,), jnp.int32)
    logical = _scatter_rows({"big": jnp.zeros((16, 1, 64, 64, 1), jnp.uint8)}, {"big": rows}, idx, e_idx)
    stored = _scatter_rows({"big": jnp.zeros((16, 1, 32, 128), jnp.uint8)}, {"big": rows}, idx, e_idx)
    assert logical["big"].shape == (16, 1, 64, 64, 1) and stored["big"].shape == (16, 1, 32, 128)
    t_idx = jnp.arange(6, dtype=jnp.int32).reshape(1, 3, 2)
    e2 = jnp.zeros((2,), jnp.int32)
    a = _gather_batch(logical, t_idx, e2, ())
    b = _gather_batch(stored, t_idx, e2, (), items=(("big", (64, 64, 1)),))
    assert a["big"].shape == b["big"].shape == (1, 3, 2, 64, 64, 1)
    np.testing.assert_array_equal(np.asarray(a["big"]), np.asarray(b["big"]))
    np.testing.assert_array_equal(np.asarray(a["big"])[0, :, 0], rows[[0, 2, 4]])
