"""DreamerV3 per-algo contract (reference sheeprl/algos/dreamer_v3/utils.py).

`Moments` is a pure pytree (low/high EMA of return percentiles) updated
functionally inside the jitted train step; the reference's `fabric.all_gather`
(:56-63) is unnecessary under the single JAX controller (the full batch is
already visible) — multi-host runs get the same semantics because the batch
is globally sharded and `jnp.quantile` runs on the global array.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

AGGREGATOR_KEYS = {
    "Rewards/rew_avg",
    "Game/ep_len_avg",
    "Loss/world_model_loss",
    "Loss/value_loss",
    "Loss/policy_loss",
    "Loss/observation_loss",
    "Loss/reward_loss",
    "Loss/state_loss",
    "Loss/continue_loss",
    "State/kl",
    "State/post_entropy",
    "State/prior_entropy",
}
MODELS_TO_REGISTER = {"world_model", "actor", "critic", "target_critic", "moments"}


class MomentsState(NamedTuple):
    low: jax.Array
    high: jax.Array


def init_moments() -> MomentsState:
    return MomentsState(low=jnp.zeros(()), high=jnp.zeros(()))


def update_moments(
    state: MomentsState,
    x: jax.Array,
    decay: float = 0.99,
    max_: float = 1.0,
    percentile_low: float = 0.05,
    percentile_high: float = 0.95,
) -> Tuple[MomentsState, jax.Array, jax.Array]:
    """Returns (new_state, offset, invscale) (reference Moments.forward :52-63)."""
    x = jax.lax.stop_gradient(x.astype(jnp.float32))
    low = jnp.quantile(x, percentile_low)
    high = jnp.quantile(x, percentile_high)
    new_low = decay * state.low + (1 - decay) * low
    new_high = decay * state.high + (1 - decay) * high
    invscale = jnp.maximum(1.0 / max_, new_high - new_low)
    return MomentsState(new_low, new_high), new_low, invscale


def prepare_obs(
    obs: Dict[str, np.ndarray], cnn_keys=(), mlp_keys=(), num_envs: int = 1
) -> Dict[str, np.ndarray]:
    """Shape the host obs for the player: images stay uint8 (normalized in
    the encoder path), vectors f32 (reference dreamer_v3/utils.py
    prepare_obs). Stays numpy — the jitted player step transfers it to
    wherever the player params are committed (parallel/placement.py), so no
    eager device round trip happens here."""
    out: Dict[str, np.ndarray] = {}
    for k in cnn_keys:
        out[k] = np.asarray(obs[k]).reshape(num_envs, *np.asarray(obs[k]).shape[-3:])
    for k in mlp_keys:
        out[k] = np.asarray(obs[k], np.float32).reshape(num_envs, -1)
    return out


def normalize_obs(obs: Dict[str, jax.Array], cnn_keys) -> Dict[str, jax.Array]:
    return {k: (v.astype(jnp.float32) / 255.0 - 0.5) if k in cnn_keys else v for k, v in obs.items()}


def use_phase_obs_loss(wm_cfg: Any, cnn_keys) -> bool:
    """True when the observation MSE should be evaluated in phase space:
    the einsum conv lowering is active (ops/conv_einsum.py) and there are
    image keys to decode. Shared by the DV3 and P2E-DV3 train programs."""
    from ...ops.conv_einsum import resolve_conv_impl

    return bool(cnn_keys) and resolve_conv_impl(str(wm_cfg.select("conv_impl", "auto")))


def decode_obs_dists(wm_apply, wm_params, wm_cls, latents, batch_obs, cnn_keys, mlp_keys, phase: bool):
    """Decoder distributions + matching observation targets for the
    reconstruction loss. ``phase=True`` decodes the cnn keys in phase space
    ([..., I, I, 2, 2, C], skipping the depth-to-space interleave whose
    backward transpose dominates the CPU gradient step) and phase-splits the
    gradient-free targets; the summed MSE is identical either way."""
    from ...distributions import MSEDistribution, SymlogDistribution
    from ...ops.conv_einsum import phase_split_nhwc

    if phase:
        recon = wm_apply(wm_params, wm_cls.decode_phases, latents)
        po = {k: MSEDistribution(recon[k], dims=5) for k in cnn_keys}
        targets = dict(batch_obs)
        for k in cnn_keys:
            targets[k] = phase_split_nhwc(batch_obs[k])
    else:
        recon = wm_apply(wm_params, wm_cls.decode, latents)
        po = {k: MSEDistribution(recon[k], dims=3) for k in cnn_keys}
        targets = batch_obs
    po.update({k: SymlogDistribution(recon[k], dims=1) for k in mlp_keys})
    return po, targets


def make_precision_applies(cfg: Any, wm, actor, critic):
    """The single mixed-precision cast boundary shared by the DV3-family
    train steps (dreamer_v3 / p2e_dv3): network forwards run in
    `fabric.precision`'s compute dtype, inputs/outputs cross in f32 so
    losses, Moments and master params stay full precision. Returns
    (wm_apply, actor_apply, critic_apply, cast, compute_dtype, mixed)."""
    import jax.numpy as jnp

    from ...ops import wgrad_hoist
    from ...parallel.mesh import cast_floating, get_precision

    compute_dtype = get_precision(str(cfg.select("fabric.precision", "32-true"))).compute_dtype
    mixed = compute_dtype != jnp.float32

    def cast(tree, dtype):
        return cast_floating(tree, dtype) if mixed else tree

    def wm_apply(p, method, *args, tape=False, perturbations=None):
        """``tape=True`` is a step inside `ops.wgrad_hoist.scan`: it hands that
        step's ``perturbations`` (None when the scan probes) to the method's
        `HoistableDense` calls and also returns what they taped."""
        variables = {"params": cast(p, compute_dtype)}
        args = cast(args, compute_dtype)
        if not tape:
            return cast(wm.apply(variables, *args, method=method), jnp.float32)
        if perturbations is not None:
            variables[wgrad_hoist.PERTURB] = perturbations
        out, taped = wm.apply(variables, *args, method=method, mutable=[wgrad_hoist.TAPE])
        return cast(out, jnp.float32), taped[wgrad_hoist.TAPE]

    def actor_apply(p, x):
        return cast(actor.apply({"params": cast(p, compute_dtype)}, cast(x, compute_dtype)), jnp.float32)

    def critic_apply(p, x):
        return cast(critic.apply({"params": cast(p, compute_dtype)}, cast(x, compute_dtype)), jnp.float32)

    return wm_apply, actor_apply, critic_apply, cast, compute_dtype, mixed


def make_ens_apply(ens_apply, cast, compute_dtype):
    """Cast-bounded ensemble forward for the P2E variants (same contract as
    the applies above)."""
    import jax.numpy as jnp

    def ens_apply_c(p, x):
        return cast(ens_apply(cast(p, compute_dtype), cast(x, compute_dtype)), jnp.float32)

    return ens_apply_c


def extract_masks(obs: Dict[str, Any], num_envs: int = 1):
    """Action-mask obs keys for the (Minedojo)Actor (reference
    dreamer_v3.py:574-577: every `mask*` obs key gates an actor head).
    Returns None when the env emits no masks, so non-masking envs never pay
    a player-step retrace."""
    masks = {
        k: np.asarray(v, bool).reshape(num_envs, -1) for k, v in obs.items() if k.startswith("mask")
    }
    return masks or None


def test(player_step, player_state, env, cfg, log_dir: str, logger=None, seed=None, device=None) -> float:
    """Greedy episode with the recurrent player (reference utils.py test).
    `player_step(obs, state, key, greedy) -> (actions, state, key)` threads
    the PRNG key through the jitted step; `device` commits the initial key
    next to the player params so no cross-device hop happens per frame."""
    done = False
    cumulative_rew = 0.0
    obs, _ = env.reset(seed=seed if seed is not None else cfg.seed)
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    key = jax.random.key(cfg.seed)
    if device is not None:
        key = jax.device_put(key, device)
    import gymnasium as gym

    is_box = isinstance(env.action_space, gym.spaces.Box)
    while not done:
        host_obs = prepare_obs(obs, cnn_keys, mlp_keys, 1)
        env_actions, player_state, key = player_step(
            host_obs, player_state, key, True, extract_masks(obs, 1)
        )
        acts = np.asarray(env_actions)
        if is_box or isinstance(env.action_space, gym.spaces.MultiDiscrete):
            step_action = acts.reshape(env.action_space.shape)
        else:
            step_action = acts.reshape(()).item()
        obs, reward, terminated, truncated, _ = env.step(step_action)
        done = bool(terminated or truncated)
        cumulative_rew += float(reward)
        if cfg.get("dry_run", False):
            done = True
    if logger is not None:
        logger.log_metrics({"Test/cumulative_reward": cumulative_rew}, 0)
    print(f"Test - Reward: {cumulative_rew}")
    env.close()
    return cumulative_rew
