"""Faults planted under the harness, for the tests that must see `correct`
come out false (tests/perfbench). Each breaks the timed path beneath the
harness's own wrappers, so the harness sees only what a broken program would
show it. Never used by a benchmark run.
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable


def _broken_train(kind: str, train: Callable) -> Callable:
    import jax
    import jax.numpy as jnp

    def unchanged(params, opt_states, moments, batches, keys):
        kept = jax.tree.map(jnp.copy, (params, opt_states, moments))
        out = train(params, opt_states, moments, batches, keys)
        return (*kept, out[3])

    def unchanged_actor(params, opt_states, moments, batches, keys):
        kept = jax.tree.map(jnp.copy, params["actor"])
        new_params, *rest = train(params, opt_states, moments, batches, keys)
        return ({**new_params, "actor": kept}, *rest)

    def half_batch(params, opt_states, moments, batches, keys):
        half = {k: v[:, :, : v.shape[2] // 2] for k, v in batches.items()}
        return train(params, opt_states, moments, half, keys)

    return {"unchanged": unchanged, "unchanged_actor": unchanged_actor, "half_batch": half_batch}[kind]


class _AlteredPrefetcher:
    """The ring's answer altered where it is produced: one pixel of one row."""

    def __init__(self, inner: Any):
        self._inner = inner

    def __getattr__(self, name: str) -> Any:
        return getattr(self._inner, name)

    def take(self, g: int) -> Any:
        batch = dict(self._inner.take(g))
        key = next(k for k, v in batch.items() if v.ndim == 6)
        batch[key] = batch[key].at[0, 3, 1, 5, 5, 0].add(1)
        return batch


@contextlib.contextmanager
def planted(kind: str):
    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3

    if kind in ("unchanged", "unchanged_actor", "half_batch"):
        name, orig = "make_train_fn", dv3.make_train_fn
        patched = lambda *a, **k: _broken_train(kind, orig(*a, **k))  # noqa: E731
    elif kind == "altered_batch":
        name, orig = "make_sequential_prefetcher", dv3.make_sequential_prefetcher
        patched = lambda *a, **k: _AlteredPrefetcher(orig(*a, **k))  # noqa: E731
    else:
        raise ValueError(f"unknown fault {kind!r}")
    setattr(dv3, name, patched)
    try:
        yield
    finally:
        setattr(dv3, name, orig)
