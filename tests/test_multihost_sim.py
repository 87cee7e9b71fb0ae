"""Simulated multi-host structure checks (VERDICT r3 item 9).

True multi-host needs several controller processes; these tests exercise the
num_nodes>1 code paths structurally by patching jax's process topology —
rank gating, the all-ranks checkpoint-conversion ordering (a collective must
run on every process), the non-addressable-shard fetch dispatch, and
multi-host opt-state sharding no longer degrading to replicated.
"""
import numpy as np
import pytest

import jax

from sheeprl_tpu.parallel.mesh import Distributed
from sheeprl_tpu.utils import checkpoint as ckpt_mod
from sheeprl_tpu.utils.checkpoint import CheckpointManager, _fetch_global


def _two_host_topology(monkeypatch, index: int = 1):
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "process_index", lambda: index)
    monkeypatch.setattr(jax.distributed, "is_initialized", lambda: True)


def test_distributed_rank_gating_under_two_hosts(monkeypatch):
    _two_host_topology(monkeypatch, index=1)
    dist = Distributed(devices=2, num_nodes=2)
    assert dist.num_nodes == 2
    assert dist.process_index == 1
    assert not dist.is_global_zero


def test_shard_over_dp_shards_under_two_hosts(monkeypatch):
    """The round-3 behavior (silent degrade to replicated on multi-host) is
    lifted: the ZeRO-1 layout shards over dp regardless of process count."""
    _two_host_topology(monkeypatch)
    dist = Distributed(devices=8, num_nodes=2)
    big = np.zeros((16, 2048), np.float32)  # divisible, above min_size
    placed = dist.shard_over_dp({"m": big})["m"]
    spec = placed.sharding.spec
    assert spec and spec[0] == "dp", f"expected dp-sharded leading axis, got {spec}"


def test_disabled_checkpoint_manager_still_converts(tmp_path, monkeypatch):
    """Non-zero ranks must still run the host conversion (it can contain an
    all-gather collective) even though only rank 0 writes the file."""
    calls = []
    real = ckpt_mod._to_host
    monkeypatch.setattr(ckpt_mod, "_to_host", lambda tree: calls.append(1) or real(tree))
    cm = CheckpointManager(str(tmp_path), enabled=False)
    out = cm.save(1, {"a": np.ones(3)})
    assert out is None and calls == [1]
    assert not list(tmp_path.rglob("*.ckpt"))


def test_fetch_global_dispatches_to_allgather(monkeypatch):
    """Arrays whose shards are not all addressable from this process go
    through multihost_utils.process_allgather."""
    from jax.experimental import multihost_utils

    class FakeGlobal:
        is_fully_addressable = False

    seen = {}

    def fake_allgather(x, tiled=False):
        seen["x"] = x
        seen["tiled"] = tiled
        return np.arange(4)

    monkeypatch.setattr(multihost_utils, "process_allgather", fake_allgather)
    out = _fetch_global(FakeGlobal())
    assert isinstance(seen["x"], FakeGlobal) and seen["tiled"] is True
    np.testing.assert_array_equal(out, np.arange(4))


def test_fetch_global_addressable_stays_local():
    x = jax.numpy.arange(5)
    np.testing.assert_array_equal(_fetch_global(x), np.arange(5))


def test_wall_clock_stopper_disabled_multi_host(monkeypatch, capsys):
    from sheeprl_tpu.config import Config
    from sheeprl_tpu.utils.utils import WallClockStopper

    _two_host_topology(monkeypatch)
    wall = WallClockStopper(Config({"algo": {"max_wall_time_s": 1}}))
    assert wall.max_s < 0  # rank-local clocks cannot coordinate a stop
    assert not wall.expired(0, 100)


@pytest.mark.slow
def test_real_two_process_multihost_dryrun():
    """No mocks: two actual controller processes jax.distributed.initialize
    against a local coordinator and run the cross-process psum / ZeRO-1 /
    allgather-checkpoint suite (scripts/multihost_dryrun.py, VERDICT r4 #4).
    The monkeypatch-based tests above stay as fast unit coverage of the same
    rank-gating logic."""
    import json
    import os
    import subprocess
    import sys as _sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [_sys.executable, os.path.join(repo, "scripts", "multihost_dryrun.py")],
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        text=True,
        timeout=600,  # > the parent's own 2 sequential 240s child budgets
        cwd=repo,
    )
    assert proc.stdout.strip(), f"parent printed nothing (rc={proc.returncode})"
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 0 and rec["ok"], rec
    assert rec["n_processes"] == 2
