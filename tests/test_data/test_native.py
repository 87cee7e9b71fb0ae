"""Native replay-gather kernel: equivalence with the numpy path."""
import numpy as np
import pytest

from sheeprl_tpu import native
from sheeprl_tpu.data import SequentialReplayBuffer


def test_gather_rows_matches_numpy():
    lib = native.load_native()
    if lib is None:
        pytest.skip("native toolchain unavailable")
    rng = np.random.default_rng(0)
    src = rng.standard_normal((128, 7)).astype(np.float32)
    idx = rng.integers(0, 128, size=(4, 5, 3))
    out = native.gather_rows(src, idx, (4, 5, 3, 7))
    assert out is not None
    np.testing.assert_array_equal(out, src[idx])


def test_sequential_sample_native_equals_fallback(monkeypatch):
    if native.load_native() is None:
        pytest.skip("native toolchain unavailable")

    def make_filled():
        # same seed → the two buffers' OWNED sample rngs draw identical indices
        rb = SequentialReplayBuffer(32, n_envs=3, obs_keys=("state",), seed=7)
        rng = np.random.default_rng(1)
        for _ in range(40):
            rb.add(
                {
                    "state": rng.standard_normal((1, 3, 6)).astype(np.float32),
                    "rewards": rng.standard_normal((1, 3, 1)).astype(np.float32),
                }
            )
        return rb

    rb_native = make_filled()
    rb_fallback = make_filled()
    s_native = rb_native.sample(4, sequence_length=5, n_samples=2)
    monkeypatch.setattr(native, "gather_rows", lambda *a, **k: None)
    s_fallback = rb_fallback.sample(4, sequence_length=5, n_samples=2)
    assert set(s_native) == set(s_fallback)
    for k in s_native:
        np.testing.assert_array_equal(s_native[k], s_fallback[k])
        assert s_native[k].shape == (2, 5, 4) + s_native[k].shape[3:]


def test_native_build_is_keyed_by_the_source_and_lives_in_the_checkout():
    """Built from replay_gather.cpp as it stands into <checkout>/.native_build
    (git-ignored), under a name that changes with the source: a stale or
    foreign .so beside the package is never what gets loaded."""
    import hashlib
    from pathlib import Path

    status = native.native_status()
    if not status["loaded"]:
        pytest.skip(f"native toolchain unavailable: {status['error']}")
    pkg = Path(native.__file__).resolve().parent
    digest = hashlib.sha256((pkg / "replay_gather.cpp").read_bytes()).hexdigest()[:16]
    path = Path(status["path"])
    assert path.parent == pkg.parents[1] / ".native_build"
    assert digest in path.name and status["error"] is None
    assert not (pkg / "_replay_gather.so").exists()  # nothing is built into the package
