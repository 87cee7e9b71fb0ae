"""Virtual multi-device CPU mesh bootstrap (shared by tests and the driver).

Multi-chip hardware is not required to validate sharding: XLA can expose N
virtual CPU devices via ``--xla_force_host_platform_device_count`` — the JAX
analogue of the reference's ``LT_DEVICES=2`` gloo-spawn trick (reference
tests/conftest.py:16-18). Two subtleties this helper owns:

* ``XLA_FLAGS`` is read when the CPU backend initializes, so it must be set
  (or raised) before any ``jax.devices()`` call.
* The platform is forced through the ``jax_platforms`` config knob, not
  left to ``JAX_PLATFORMS``: a machine with a chip sets that variable to
  ``tpu,cpu``, and a test run or dry run that asked for a *virtual* mesh must
  not take the chip (one process holds it at a time) because of where it was
  started.
"""
from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def force_virtual_cpu_mesh(n_devices: int) -> None:
    """Ensure ≥ ``n_devices`` virtual CPU devices and force the cpu platform.

    Must run before the JAX backend initializes (i.e. before the first
    ``jax.devices()``/array op in the process). Raises RuntimeError if the
    backend still comes up short — e.g. it was already initialized.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = f"{flags} {_COUNT_FLAG}={n_devices}".strip()
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = re.sub(
            rf"{_COUNT_FLAG}=\d+", f"{_COUNT_FLAG}={n_devices}", flags
        )

    import jax

    jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"Could not provision {n_devices} virtual CPU devices "
            f"(got {len(jax.devices())}); the JAX backend was likely initialized "
            "before XLA_FLAGS could take effect — call this in a fresh process, "
            "before any jax.devices()/array operation."
        )
