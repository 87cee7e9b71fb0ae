"""Actor/learner placement unit tests (parallel/placement.py).

The donation-alias regression matters on single-device CPU runs: the learner
and the player share cpu:0, `jax.device_put` aliases instead of copying, and
the learner's donated train step would delete the mirror's buffers out from
under the player (the crash surfaced as "Buffer has been deleted or donated"
in the DreamerV3 async-refresh bench leg).
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.parallel import placement
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


_MIN = placement.AUTO_ACCELERATOR_MIN_BYTES


@pytest.mark.parametrize(
    "player_bytes,on_learner",
    [(None, False), (0, False), (_MIN - 1, False), (_MIN, True), (8 * _MIN, True)],
    ids=["no_tree", "empty", "under", "at", "over"],
)
def test_player_device_auto_resolves_by_the_bytes_the_player_reads(player_bytes, on_learner):
    """On an accelerator `auto` keeps a player of at least the threshold on the
    learner's device and sends a smaller one (a PPO or SAC MLP, DreamerV3-S)
    to the host as before; by name nothing changes."""
    chip = _FakeChip()
    assert (player_device(_ModeCfg("auto"), chip, player_bytes) is chip) == on_learner
    if not on_learner:
        assert player_device(_ModeCfg("auto"), chip, player_bytes) is host_device()
    assert player_device(_ModeCfg("host"), chip, player_bytes) is host_device()
    assert player_device(_ModeCfg("accelerator"), chip, player_bytes) is chip


@pytest.mark.parametrize("player_bytes", [None, 0, _MIN, 8 * _MIN])
def test_player_device_auto_with_a_cpu_learner_is_the_learner_whatever_the_bytes(player_bytes):
    learner = jax.local_devices(backend="cpu")[1]  # not the default device, so that the two can be told apart
    assert player_device(_ModeCfg("auto"), learner, player_bytes) is learner
    assert player_device(None, None, player_bytes) is jax.local_devices()[0]


def _slow_burst(seconds_worth: int = 400):
    """A donating 'train burst' that keeps one CPU core busy for a second or so."""

    def burst(params, x):
        x = jax.lax.fori_loop(0, seconds_worth, lambda _, y: jnp.tanh(y @ y) + 1e-3, x)
        return jax.tree.map(lambda p: p + 1.0 + 0.0 * x[0, 0], params), x

    return jax.jit(burst, donate_argnums=(0,))


def test_same_device_refresh_is_one_dispatch_that_waits_for_nothing(monkeypatch):
    """Learner and player on one device: `refresh` copies every leaf with ONE
    jitted program, returns futures while the burst that writes the
    parameters is still running, and never calls `block_until_ready`."""
    dev = host_device()
    params = jax.device_put({"w": jnp.ones((4, 4)), "b": jnp.zeros((4,)), "deep": {"k": jnp.ones((2,))}}, dev)
    x = jax.device_put(jnp.eye(384) * 0.5, dev)
    burst = _slow_burst()
    jax.block_until_ready(burst(jax.tree.map(jnp.copy, params), x))  # compiled, outside the timing
    mirror = ParamMirror(params, dev)
    assert mirror.same_device

    calls = []
    copy_leaves = placement._copy_leaves
    monkeypatch.setattr(placement, "_copy_leaves", lambda leaves: calls.append(len(leaves)) or copy_leaves(leaves))

    def never(*_a, **_k):
        raise AssertionError("a same-device refresh must not block")

    monkeypatch.setattr(jax, "block_until_ready", never)
    t0 = time.perf_counter()
    params, x = burst(params, x)
    mirror.refresh(params)
    returned = time.perf_counter() - t0
    still_running = not x.is_ready()
    monkeypatch.undo()
    jax.block_until_ready(x)
    finished = time.perf_counter() - t0
    assert calls == [3]  # one dispatch, all three leaves in it
    assert still_running and returned < 0.5 * finished, (returned, finished)
    np.testing.assert_allclose(np.asarray(mirror.current()["w"]), 2 * np.ones((4, 4)))
    np.testing.assert_allclose(np.asarray(mirror.current()["deep"]["k"]), 2 * np.ones((2,)))


@pytest.mark.parametrize("async_refresh", [False, True], ids=["blocking", "async"])
def test_same_device_mirror_survives_donation_with_a_player_thread_reading(async_refresh):
    """Several bursts, each donating the parameters the mirror was refreshed
    from, while a player thread reads `current()` all the time: it never sees
    a deleted buffer, and what it reads only ever moves forward."""
    dev = host_device()
    params = jax.device_put({"w": jnp.ones((8, 8)), "b": jnp.ones((8,))}, dev)
    mirror = ParamMirror(params, dev, async_refresh=async_refresh)
    consume = _donating_consumer()
    seen, errors, stop = [], [], threading.Event()

    def player():
        try:
            while not stop.is_set():
                cur = mirror.current()
                seen.append((float(np.asarray(cur["w"])[0, 0]), float(np.asarray(cur["b"])[0])))
        except Exception as e:  # "Buffer has been deleted or donated"
            errors.append(e)

    th = threading.Thread(target=player)
    th.start()
    try:
        for _ in range(8):
            params = consume(params)  # donates what the mirror last copied from
            mirror.refresh(params)
            time.sleep(0.01)
    finally:
        stop.set()
        th.join()
    assert not errors, errors
    assert all(w == b for w, b in seen)  # never half of one tree and half of another
    values = [w for w, _ in seen]
    assert values == sorted(values) and set(values) <= {float(i) for i in range(1, 10)}
    jax.block_until_ready(params)
    assert float(np.asarray(mirror.current()["w"])[0, 0]) == 9.0


def test_read_subtree_keeps_what_a_probe_reads_through_nested_jits():
    tree = {"a": {"w": jnp.ones((3, 3)), "unused": jnp.ones((2,))}, "b": {"w": jnp.ones((3,))}, "c": {"w": jnp.ones((5,))}}
    inner = jax.jit(lambda t, x: x @ t["a"]["w"] + t["b"]["w"])
    select = placement.read_subtree(tree, lambda t, x: (inner(t, x), 0.0 * t["c"]["w"].sum() * 0.0), jnp.ones((3,)))
    other = jax.tree.map(lambda x: x + 1, {**tree, "d": {"w": jnp.zeros((1,))}})
    picked = select(other)
    assert jax.tree.structure(picked) == jax.tree.structure({"a": {"w": 0}, "b": {"w": 0}, "c": {"w": 0}})
    assert picked["a"]["w"] is other["a"]["w"]


def test_dreamer_v3_player_acts_bit_identically_from_the_leaves_it_reads():
    """The DreamerV3 mirror holds the leaves of {wm, actor} that `make_player`'s
    programs read: first state, masked reset, actions, state and key are
    bit-identical from that subset and from the whole tree, and the decoder and
    the reward and continue heads are not in it."""
    import gymnasium as gym

    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.parallel import Distributed
    from tests.dreamer_tiny import N_ACT, TINY_DV3

    cfg = compose("config", TINY_DV3 + ["algo.mlp_keys.encoder=[state]"])
    n = 3
    space = gym.spaces.Dict(
        {"rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8), "state": gym.spaces.Box(-1, 1, (5,), np.float32)}
    )
    wm, actor, _, params = build_agent(Distributed(devices=1), cfg, space, [N_ACT], False, jax.random.key(0))
    whole = {"wm": params["wm"], "actor": params["actor"]}
    init, step = dv3.make_player(wm, actor, cfg, [N_ACT], False, n)
    view = dv3.player_params_view(init, step, params, space, ("rgb",), ("state",), n)
    subset = view(params)

    assert {"encoder", "rssm"} <= set(subset["wm"])
    assert not set(subset["wm"]) & {"observation_model", "reward", "continue"}
    # `WorldModel.initial_states` draws z0 from the transition model, so it stays (ISSUE 29 counted it among the unread)
    assert set(subset["wm"]["rssm"]) == set(whole["wm"]["rssm"])
    assert set(subset["actor"]) == set(whole["actor"])
    assert placement.tree_bytes(subset) < 0.6 * placement.tree_bytes(whole)
    assert all(a is b for a, b in zip(jax.tree.leaves(subset), jax.tree.leaves(view(whole))))

    rng = np.random.default_rng(0)
    frames = rng.integers(0, 255, (4, n, 64, 64, 3), np.uint8)

    def rollout(tree):
        key = jax.random.key(5)
        state = init(tree)
        outs = [state]
        for t in range(4):
            obs = {"rgb": frames[t], "state": np.full((n, 5), 0.1 * t, np.float32)}
            env_actions, cat, state, key = step(tree, obs, state, key, greedy=(t == 3))
            outs += [env_actions, cat, state, jax.random.key_data(key)]
            if t == 1:
                state = init(tree, np.array([True, False, True]), state)
                outs.append(state)
        return jax.tree.leaves(outs)

    for a, b in zip(rollout(whole), rollout(subset), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


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


# -- the in-order mirror: a blocking mirror on the learner's own device aliases -----------------
def _pointers(tree):
    return [x.unsafe_buffer_pointer() for x in jax.tree.leaves(tree)]


def test_in_order_mirror_on_the_learners_device_is_the_learners_own_buffers_and_follows_a_donated_update(monkeypatch):
    """Acting and the update serial on one stream (`in_order`): no copy program is dispatched, nothing is
    allocated, `Time/param_refresh` counts 0 bytes, and after an update that donates the parameters the refresh
    re-points the mirror at the new ones."""
    from sheeprl_tpu.telemetry.spans import GLOBAL_TRACKER

    dev = host_device()
    params = jax.device_put({"w": jnp.ones((4, 4)), "b": jnp.zeros((4,)), "deep": {"k": jnp.ones((2,))}}, dev)
    monkeypatch.setattr(placement, "_copy_leaves", lambda leaves: pytest.fail("an in-order mirror copies nothing"))
    mirror = ParamMirror(params, dev, in_order=True)
    assert mirror.same_device and mirror.aliased and mirror.copied_bytes == 0
    assert _pointers(mirror.current()) == _pointers(params)
    assert all(x.sharding == jax.sharding.SingleDeviceSharding(dev) for x in jax.tree.leaves(mirror.current()))
    step = _donating_consumer()
    GLOBAL_TRACKER.compute(reset=True)
    for i in range(3):
        stale = mirror.current()
        params = step(params)  # donates what the mirror points at
        assert all(x.is_deleted() for x in jax.tree.leaves(stale))
        mirror.refresh(params)
        assert _pointers(mirror.current()) == _pointers(params)
        np.testing.assert_allclose(np.asarray(mirror.current()["w"]), (2.0 + i) * np.ones((4, 4)))
    assert GLOBAL_TRACKER.sums()["Time/param_refresh"] == {"leaves": 9, "bytes": 0, "same_device": 3}
    GLOBAL_TRACKER.compute(reset=True)


@pytest.mark.parametrize("kind", ["async", "another_device", "not_said"])
def test_only_a_blocking_in_order_mirror_on_the_learners_device_aliases(kind):
    """The async mirror, the mirror on another device and the mirror of a caller that says nothing copy as before:
    new buffers, the bytes counted, and the parameters they were made from may be donated at once."""
    dev = host_device()
    params = jax.device_put({"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}, dev)
    target = jax.devices()[1] if kind == "another_device" else dev
    mirror = ParamMirror(params, target, async_refresh=kind == "async", in_order=kind != "not_said")
    assert not mirror.aliased and mirror.copied_bytes == (16 + 4) * 4
    assert mirror.same_device == (kind != "another_device")
    if kind != "another_device":
        assert not set(_pointers(mirror.current())) & set(_pointers(params))
    params = _donating_consumer()(params)
    np.testing.assert_allclose(np.asarray(mirror.current()["w"]), np.ones((4, 4)))  # its own copy outlives the donation


def test_make_param_mirror_aliases_only_for_a_caller_that_is_in_order_and_blocking():
    from sheeprl_tpu.config import Config

    params = jax.device_put({"w": jnp.ones((4, 8))}, jax.devices()[0])
    said = {(False, True): "alias", (False, False): "copy", (True, True): "copy", (True, False): "copy"}
    for (allow_async, in_order), refresh in said.items():
        cfg = Config({"algo": {"player": {"async_refresh": True}}})
        mirror, _, _, _ = placement.make_param_mirror(cfg, jax.devices()[0], params, jax.random.key(0), allow_async=allow_async, in_order=in_order)
        assert mirror.placement["refresh"] == refresh and mirror.placement["same_device"] == 1, (allow_async, in_order)
