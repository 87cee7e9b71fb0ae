"""Pad-invariant act cores shared by both fleet act modes.

The Sebulba refactor (Podracer, arXiv:2104.06272) moves acting off the
worker hosts onto one learner-side batched inference service — but the
Ratio-ledger parity proof and the act-parity gate require that moving the
computation does not move the numbers. The classic failure mode is RNG
shape coupling: a policy that draws one batch-shaped noise tensor produces
different per-row samples the moment the batch is padded to a power-of-two
bucket or coalesced with another worker's rows.

These cores make parity hold *by construction*: every act function takes
**per-row PRNG keys** and is the ``vmap`` of a single-row step, so row
``i``'s output depends only on ``(params, obs[i], key[i], state[i])`` —
never on the batch width it happened to ride in. The worker-host mode and
the inference-service mode both call the exact same jitted core; the
service recomputes the same row keys from the base key the worker ships
(``row_keys``: ``fold_in(key, slot)`` per env slot), so a row acted
locally and a row acted remotely are the same computation on the same
operands. The compiled program still depends on the batch width, and with
it the last bit of a float may: bit equality holds at equal width, float32
rounding across widths (see :mod:`~sheeprl_tpu.fleet.act_service`).

Cores expose the surface :mod:`sheeprl_tpu.fleet.act_service` batches
behind and :mod:`sheeprl_tpu.fleet.programs` steps locally:

* ``extract_params(params_np)`` — the acting subtree of a publication;
* ``act(params, obs, keys, state, mask)`` →
  ``(env_actions, actions_cat, new_state)`` (stateless cores return
  ``None`` for the latter two);
* stateful cores (DreamerV3 ``(h, z, a)`` latents) add
  ``init_state(params, n)`` / ``reset_state(params, mask, state)``.
"""
from __future__ import annotations

import itertools
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

__all__ = ["ActCore", "build_act_core", "row_keys"]

_CORE_TAG = itertools.count(1)


def row_keys(key: Any, n: int) -> Any:
    """Per-row keys for one act call: ``fold_in(key, slot)`` for each of the
    ``n`` env slots. Deterministic in (key, slot) alone, so the inference
    service reproduces a worker's rows from the shipped base key regardless
    of padding or cross-worker coalescing."""
    import jax
    import jax.numpy as jnp

    return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(key, jnp.arange(int(n)))


class ActCore:
    """Base surface; concrete cores fill in the callables."""

    name = "act"
    stateful = False

    def extract_params(self, params_np: Any) -> Any:
        return params_np

    def act(
        self, params: Any, obs: Any, keys: Any, state: Any = None, mask: Any = None
    ) -> Tuple[Any, Any, Any]:
        raise NotImplementedError

    def init_state(self, params: Any, n: int) -> Any:
        raise NotImplementedError(f"{self.name} is stateless")

    def reset_state(self, params: Any, mask: Any, state: Any) -> Any:
        raise NotImplementedError(f"{self.name} is stateless")


class _SacActCore(ActCore):
    """Feed-forward tanh-Gaussian SAC actor, one noise draw per row key."""

    name = "sac"
    stateful = False

    def __init__(self, cfg: Any, obs_space: Any, action_space: Any) -> None:
        import jax
        import jax.numpy as jnp

        from ..algos.sac.agent import SACActor
        from ..telemetry import xla as _xla

        self.act_dim = int(np.prod(action_space.shape))
        actor = SACActor(
            action_dim=self.act_dim,
            hidden_size=cfg.algo.actor.hidden_size,
            action_low=action_space.low.tolist(),
            action_high=action_space.high.tolist(),
        )

        def _row(params: Any, obs_row: Any, key_row: Any) -> Any:
            mean, log_std = actor.apply({"params": params}, obs_row[None])
            std = jnp.exp(log_std)
            x_t = mean + std * jax.random.normal(key_row, mean.shape)
            y_t = jnp.tanh(x_t)
            return (y_t * actor.action_scale + actor.action_bias)[0]

        batched = jax.vmap(_row, in_axes=(None, 0, 0))
        self._act = jax.jit(
            _xla.RETRACE_DETECTOR.wrap(batched, f"fleet.act_core[sac]#{next(_CORE_TAG)}")
        )

    def extract_params(self, params_np: Any) -> Any:
        return params_np["actor"]

    def act(
        self, params: Any, obs: Any, keys: Any, state: Any = None, mask: Any = None
    ) -> Tuple[Any, Any, Any]:
        return self._act(params, obs, keys), None, None


class _DreamerActCore(ActCore):
    """Recurrent DV3 player as a vmapped single-row step: the world-model
    recurrence, representation sample and actor sample all run per row with
    that row's split of its own key — the row-shaped twin of
    ``dreamer_v3.make_player`` (same math, pad-invariant RNG)."""

    name = "dreamer_v3"
    stateful = True

    def __init__(self, cfg: Any, obs_space: Any, action_space: Any) -> None:
        import gymnasium as gym
        import jax
        import jax.numpy as jnp

        from ..algos.dreamer_v3.agent import WorldModel, build_agent, sample_actor_actions
        from ..algos.dreamer_v3.utils import normalize_obs
        from ..parallel.mesh import Distributed
        from ..telemetry import xla as _xla

        self.is_continuous = isinstance(action_space, gym.spaces.Box)
        is_multidiscrete = isinstance(action_space, gym.spaces.MultiDiscrete)
        if self.is_continuous:
            self.actions_dim = [int(np.prod(action_space.shape))]
        elif is_multidiscrete:
            self.actions_dim = [int(n) for n in action_space.nvec]
        else:
            self.actions_dim = [int(action_space.n)]
        self.act_total = int(sum(self.actions_dim))
        cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
        # module defs only — the init params are discarded; real snapshots
        # arrive through extract_params at every publication
        dist = Distributed(devices=1, accelerator="cpu")
        wm, actor, _critic, _params = build_agent(
            dist, cfg, obs_space, self.actions_dim, self.is_continuous,
            jax.random.PRNGKey(0), None,
        )
        self._wm = wm
        is_continuous = self.is_continuous

        def _row(params: Any, obs_row: Any, state_row: Any, key_row: Any, mask_row: Any) -> Any:
            obs = {k: v[None] for k, v in obs_row.items()}
            h, z, a = (s[None] for s in state_row)
            obs = normalize_obs(obs, cnn_keys)
            embedded = wm.apply({"params": params["wm"]}, obs, method=WorldModel.embed)
            h = wm.apply(
                {"params": params["wm"]},
                jnp.concatenate([z, a], -1),
                h,
                method=WorldModel.recurrent_step,
            )
            k1, k2 = jax.random.split(key_row)
            z = wm.apply(
                {"params": params["wm"]}, h, embedded, k1, method=WorldModel.representation_step
            )
            pre = actor.apply({"params": params["actor"]}, jnp.concatenate([z, h], -1))
            acts, _ = sample_actor_actions(actor, pre, k2, mask=mask_row)
            a = jnp.concatenate(acts, -1)
            if is_continuous:
                env_actions = a
            else:
                env_actions = jnp.stack([jnp.argmax(x, axis=-1) for x in acts], axis=-1)
            return env_actions[0], a[0], (h[0], z[0], a[0])

        tag = f"fleet.act_core[dreamer_v3]#{next(_CORE_TAG)}"
        no_mask = jax.vmap(
            lambda p, o, s, k: _row(p, o, s, k, None), in_axes=(None, 0, 0, 0)
        )
        self._act_nomask = jax.jit(_xla.RETRACE_DETECTOR.wrap(no_mask, tag))
        self._act_mask = jax.jit(
            _xla.RETRACE_DETECTOR.wrap(
                jax.vmap(_row, in_axes=(None, 0, 0, 0, 0)), tag + "/masked"
            )
        )

        @jax.jit
        def _reset(params: Any, mask: Any, state: Any) -> Any:
            n = mask.shape[0]
            h0, z0 = wm.apply(
                {"params": params["wm"]}, (n,), method=WorldModel.initial_states
            )
            a0 = jnp.zeros((n, self.act_total))
            h, z, a = state
            m = mask[:, None]
            return (jnp.where(m, h0, h), jnp.where(m, z0, z), jnp.where(m, a0, a))

        self._reset = _reset
        self._WorldModel = WorldModel

    def extract_params(self, params_np: Any) -> Any:
        return {"wm": params_np["wm"], "actor": params_np["actor"]}

    def act(
        self, params: Any, obs: Any, keys: Any, state: Any = None, mask: Any = None
    ) -> Tuple[Any, Any, Any]:
        if mask is None:
            return self._act_nomask(params, obs, state, keys)
        return self._act_mask(params, obs, state, keys, mask)

    def init_state(self, params: Any, n: int) -> Any:
        import jax.numpy as jnp

        h0, z0 = self._wm.apply(
            {"params": params["wm"]}, (int(n),), method=self._WorldModel.initial_states
        )
        return (h0, z0, jnp.zeros((int(n), self.act_total)))

    def reset_state(self, params: Any, mask: Any, state: Any) -> Any:
        import jax.numpy as jnp

        return self._reset(params, jnp.asarray(mask, bool), state)


_BUILDERS: Dict[str, Callable[..., ActCore]] = {
    "sac": _SacActCore,
    "dreamer_v3": _DreamerActCore,
}


def build_act_core(name: str, cfg: Any, obs_space: Any, action_space: Any) -> ActCore:
    """The one core per algorithm both act modes share. ``name`` is the
    fleet program name (``sac`` / ``dreamer_v3``); unknown names mean the
    algorithm has no batched act path (PPO's strict on-policy rollouts stay
    worker-hosted)."""
    if name not in _BUILDERS:
        raise ValueError(
            f"no act core for program '{name}' (batched acting supports: "
            f"{sorted(_BUILDERS)})"
        )
    return _BUILDERS[name](cfg, obs_space, action_space)
