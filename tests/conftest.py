"""Test fixtures (counterpart of reference tests/conftest.py).

Forces CPU-JAX with 8 virtual devices — the analogue of the reference's
LT_DEVICES=2 gloo-spawn trick (conftest.py:16-18): multi-device sharding is
exercised without TPU hardware.

The platform is pinned to the CPU in code as well (see
`force_virtual_cpu_mesh`), so the suite never takes the chip of a machine
that has one, whatever JAX_PLATFORMS says there.
"""
from sheeprl_tpu.utils.virtual_mesh import force_virtual_cpu_mesh

force_virtual_cpu_mesh(8)

import pytest


@pytest.fixture(autouse=True)
def chdir_tmp(tmp_path, monkeypatch):
    """Each test runs in a fresh cwd so logs/ and memmaps don't leak."""
    monkeypatch.chdir(tmp_path)
    yield


@pytest.fixture(params=["1", "2"])
def devices(request):
    """Parametrize over 1 and 2 mesh devices (reference conftest devices)."""
    return request.param


@pytest.fixture()
def standard_args():
    return [
        "dry_run=True",
        "env.num_envs=2",
        "env.sync_env=True",
        "env.capture_video=False",
        "buffer.memmap=False",
        "metric.log_level=0",
        "checkpoint.save_last=False",
    ]


def pytest_collection_modifyitems(config, items):
    # `full` implies `slow`: `-m "not slow"` must keep excluding the broad
    # e2e matrix even though addopts' `-m "not full"` is overridden by any
    # CLI-provided -m expression
    for item in items:
        if "full" in item.keywords:
            item.add_marker(pytest.mark.slow)
