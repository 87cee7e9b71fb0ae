"""Actor/learner placement unit tests (parallel/placement.py).

The donation-alias regression matters on single-device CPU runs: the learner
and the player share cpu:0, `jax.device_put` aliases instead of copying, and
the learner's donated train step would delete the mirror's buffers out from
under the player (the crash surfaced as "Buffer has been deleted or donated"
in the DreamerV3 async-refresh bench leg).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.parallel.placement import ParamMirror, host_device, player_device


def _donating_consumer():
    @jax.jit
    def step(params):
        return jax.tree.map(lambda x: x + 1.0, params)

    return jax.jit(lambda p: step(p), donate_argnums=(0,))


def test_param_mirror_survives_donation_blocking():
    dev = host_device()
    params = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}
    params = jax.device_put(params, dev)
    mirror = ParamMirror(params, dev, async_refresh=False)
    consume = _donating_consumer()
    params = consume(params)  # donates the originals
    # the mirror's copy must still be readable
    np.testing.assert_allclose(np.asarray(mirror.current()["w"]), np.ones((4, 4)))
    mirror.refresh(params)
    params = consume(params)  # donates what the mirror was refreshed from
    np.testing.assert_allclose(np.asarray(mirror.current()["w"]), 2 * np.ones((4, 4)))


def test_param_mirror_survives_donation_async():
    dev = host_device()
    params = jax.device_put({"w": jnp.ones((2, 2))}, dev)
    mirror = ParamMirror(params, dev, async_refresh=True)
    consume = _donating_consumer()
    for i in range(4):  # params value: 1 → i+2 after the i-th consume
        params = consume(params)
        mirror.refresh(params)
        # async mode may serve the previous copy; it must never serve a
        # donated buffer
        val = float(np.asarray(mirror.current()["w"])[0, 0])
        assert val in (float(i + 1), float(i + 2))
    # once everything has landed the newest copy wins
    jax.block_until_ready(params)
    np.testing.assert_allclose(np.asarray(mirror.current()["w"]), 5 * np.ones((2, 2)))


def test_param_mirror_survives_donation_of_mesh_replicated_params():
    """A leaf replicated over a multi-device mesh has a copy on the player's
    device too: `device_put` to that device aliases it, and the learner's
    donation then deleted the mirror (found by chip_smoke's four-device
    rehearsal: "Buffer has been deleted or donated" on the player thread)."""
    from sheeprl_tpu.parallel import Distributed

    dist = Distributed(devices=2)
    params = dist.replicate({"w": jnp.ones((4, 4))})
    mirror = ParamMirror(params, dist.local_device)
    consume = _donating_consumer()
    params = consume(params)  # donates both devices' copies
    np.testing.assert_allclose(np.asarray(mirror.current()["w"]), np.ones((4, 4)))
    mirror.refresh(params)
    params = consume(params)
    np.testing.assert_allclose(np.asarray(mirror.current()["w"]), 2 * np.ones((4, 4)))


def test_player_device_auto_on_cpu_mesh_is_default():
    # CPU-only process: auto keeps the player on the default device
    assert player_device(None).platform == "cpu"


def test_accelerator_asked_for_by_name_and_absent_is_an_error():
    """`fabric.accelerator=tpu` on a CPU-only process (this one) raises; it
    used to carry on with whatever `jax.devices()` returned."""
    from sheeprl_tpu.config import Config
    from sheeprl_tpu.parallel import Distributed, build_distributed

    with pytest.raises(RuntimeError):
        Distributed(devices=1, accelerator="tpu")
    with pytest.raises(RuntimeError):
        build_distributed(Config({"fabric": {"devices": 1, "accelerator": "tpu"}}))
    assert Distributed(devices=1, accelerator="cpu").local_device.platform == "cpu"
    assert Distributed(devices=1, accelerator="auto").local_device.platform == "cpu"


class _ModeCfg:
    def __init__(self, mode):
        self._mode = mode

    def select(self, *_a, **_k):
        return self._mode


class _FakeChip:
    platform = "tpu"


def test_player_device_host_needs_a_cpu_backend_and_auto_stays_on_the_learner(monkeypatch):
    """A process without a CPU backend (e.g. JAX_PLATFORMS=tpu): `host` is an
    error, not quietly the chip; `auto` keeps what it always chose there,
    the learner's device."""

    def no_cpu_backend(backend=None):
        raise RuntimeError("Unknown backend cpu")

    chip = _FakeChip()
    assert player_device(_ModeCfg("auto"), chip) is host_device()  # this process has one
    monkeypatch.setattr(jax, "local_devices", no_cpu_backend)
    with pytest.raises(RuntimeError):
        player_device(_ModeCfg("host"), chip)
    assert player_device(_ModeCfg("auto"), chip) is chip
    assert player_device(_ModeCfg("accelerator"), chip) is chip


def test_param_mirror_commits_every_leaf_to_its_one_device():
    """Mesh-placed learner params must reach the player as single-device
    arrays like its key and state: a leaf that kept the mesh sharding made
    the DreamerV3 player step trace twice under jax 0.9 (its key came back
    from the first call typed with the params' mesh)."""
    from jax.sharding import SingleDeviceSharding

    from sheeprl_tpu.parallel import Distributed

    dist = Distributed(devices=1)
    params = dist.replicate({"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))})
    assert not isinstance(params["w"].sharding, SingleDeviceSharding)
    mirror = ParamMirror(params, dist.local_device)
    leaves = jax.tree.leaves(mirror.current())
    assert all(isinstance(x.sharding, SingleDeviceSharding) for x in leaves)
    mirror.refresh(jax.tree.map(lambda x: x + 1, params))
    assert all(isinstance(x.sharding, SingleDeviceSharding) for x in jax.tree.leaves(mirror.current()))

    @jax.jit
    def step(p, key):
        key, sub = jax.random.split(key)
        return p["w"].sum() + jax.random.normal(sub), key

    key = jax.device_put(jax.random.key(0), dist.local_device)
    for _ in range(3):
        _, key = step(mirror.current(), key)
    assert step._cache_size() == 1


def test_player_device_rejects_unknown_mode():
    class _Cfg:
        def select(self, *_a, **_k):
            return "bogus"

    with pytest.raises(ValueError):
        player_device(_Cfg())


class _WallCfg:
    """Minimal cfg shim: select() over a flat dict + attribute checkpoint."""

    def __init__(self, max_wall, save_last):
        self._d = {"algo.max_wall_time_s": max_wall}

        class _Ckpt:
            pass

        self.checkpoint = _Ckpt()
        self.checkpoint.save_last = save_last

    def select(self, path, default=None):
        return self._d.get(path, default)


def test_wall_clock_stopper_and_cap_helper():
    from sheeprl_tpu.utils.utils import WallClockStopper, wall_cap_reached

    saves = []

    class _Ckpt:
        def save(self, step, state):
            saves.append((step, state))

    # budget not spent → no stop, no save
    wall = WallClockStopper(_WallCfg(3600.0, True))
    assert not wall_cap_reached(wall, 10, 100, _Ckpt(), lambda: {"s": 1}, _WallCfg(3600.0, True))
    assert saves == []

    # spent budget → stop; save gated on checkpoint.save_last
    wall = WallClockStopper(_WallCfg(1e-9, False))
    assert wall_cap_reached(wall, 10, 100, _Ckpt(), lambda: {"s": 1}, _WallCfg(1e-9, False))
    assert saves == []
    wall = WallClockStopper(_WallCfg(1e-9, True))
    assert wall_cap_reached(wall, 12, 100, _Ckpt(), lambda: {"s": 2}, _WallCfg(1e-9, True))
    assert saves == [(12, {"s": 2})]

    # disabled (default -1) → never stops
    wall = WallClockStopper(_WallCfg(-1, True))
    assert not wall.expired(0, 100)
