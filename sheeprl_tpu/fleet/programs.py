"""Worker-side acting programs for the adopted algorithms.

A *program* is what a fleet worker process runs between packets: it owns
the worker's env slice and its host-CPU policy, and replays the exact
env-interaction logic of the algorithm's in-process ``interact()`` closure —
restricted to ``envs_per_worker`` columns — into the packet's
``RecordingSink``. All heavy imports happen lazily inside the builder
functions: this module is imported BY PATH inside the worker process (the
spawn args stay picklable strings), and must stay light for the learner
process which imports it only for the numpy-only merge helpers.

Seeding contract: worker ``w`` builds env columns ``[w·epw, (w+1)·epw)``
with the *same per-env seeds* the in-process ``vectorize`` would give
those columns, so the env streams are identical modulo action divergence.

Programs expose:

* ``sync_params`` — False for the off-policy step programs (act with the
  newest snapshot available, stale is fine), True for PPO (exactly one
  rollout per publication: the strict on-policy round protocol);
* ``set_params(params_np, version)``;
* ``step(sink) -> (env_steps, payload_or_None)`` — None means "the sink is
  the payload".
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Tuple

import numpy as np

__all__ = ["dreamer_v3_program", "merge_ppo_round", "ppo_program", "sac_program"]


def _act_mode(cfg: Any) -> str:
    sel = cfg.select if hasattr(cfg, "select") else (lambda p, d=None: d)
    return str(sel("fleet.act_mode", "worker") or "worker")


def _act_timeout(cfg: Any) -> float:
    sel = cfg.select if hasattr(cfg, "select") else (lambda p, d=None: d)
    v = sel("fleet.act.timeout_s", None)
    return float(30.0 if v is None else v)


def _remote_act(program: Any, req: Dict[str, Any]) -> Dict[str, Any]:
    """Ship one act request through the worker's channel (Sebulba mode) and
    block for the batched response, emitting the `act_submit` span the trace
    merger pairs with the service's `act_infer`. The channel is injected by
    the worker loop (``act_transport`` / ``act_identity``); request ids are
    a per-incarnation counter, so the service's idempotency cache can tell a
    retry from a new request."""
    from ..telemetry import tracing

    transport = getattr(program, "act_transport", None)
    identity = getattr(program, "act_identity", None)
    if transport is None or identity is None:
        raise RuntimeError(
            "fleet.act_mode=inference requires the worker loop's act transport "
            "(program ran outside fleet_worker_loop?)"
        )
    program._act_seq = int(getattr(program, "_act_seq", 0)) + 1
    ctx = tracing.TraceContext(tracing.new_trace_id(), tracing.new_span_id())
    req = dict(req)
    req["worker_id"] = int(identity[0])
    req["incarnation"] = int(identity[1])
    req["req_id"] = int(program._act_seq)
    req["trace"] = (ctx.trace_id, ctx.span_id)
    t0 = time.time()
    resp = transport.act_request(
        req,
        timeout_s=float(getattr(program, "act_timeout_s", 30.0)),
        beat=getattr(program, "beat", None),
    )
    t1 = time.time()
    emit = getattr(program, "trace_emit", None)
    if emit is not None:
        emit(  # lint: ok[hot-loop-emit] — one act_submit span per slice (same cadence as env_step)
            tracing.span_record(
                "act_submit",
                "worker",
                ctx,
                t0,
                t1,
                worker=req["worker_id"],
                seq=req["req_id"],
                version=int(resp.get("version", 0) or 0),
            )
        )
    if resp.get("error"):
        raise RuntimeError(f"act service error: {resp['error']}")
    return resp


def _slice_cfg(cfg: Any, epw: int) -> Any:
    """The worker's view of the run config: its env slice, no videos (the
    learner owns logging), retries/restart policy inherited unchanged."""
    from ..config import Config

    return Config(
        {
            **cfg.to_dict(),
            "env": {**cfg.env.to_dict(), "num_envs": int(epw), "capture_video": False},
        }
    )


def _slice_seed(cfg: Any, worker_id: int, epw: int) -> int:
    # the in-process vectorize seeds env i with `seed + rank*num_envs + i`; the fleet
    # is rank-0/single-controller, so column w*epw+j gets seed + w*epw + j
    return int(cfg.seed) + worker_id * epw


# ---------------------------------------------------------------------------
# SAC — one vector step per packet (uniform fixed-width replay; concat merge)
# ---------------------------------------------------------------------------
def sac_program(cfg: Any, worker_id: int, num_workers: int) -> Any:
    import jax

    from ..algos.sac.utils import flatten_obs
    from ..utils.env import episode_stats, vectorize
    from .act_core import build_act_core, row_keys

    class _SacProgram:
        sync_params = False

        def __init__(self) -> None:
            num_envs = int(cfg.env.num_envs)
            self.epw = num_envs // int(num_workers)
            self.num_workers = int(num_workers)
            wcfg = _slice_cfg(cfg, self.epw)
            self.envs = vectorize(wcfg, _slice_seed(cfg, worker_id, self.epw), 0, None)
            self.action_space = self.envs.single_action_space
            self.mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
            self.act_dim = int(np.prod(self.action_space.shape))
            self.validate = bool(cfg.buffer.validate_args)
            self.learning_starts = int(cfg.algo.learning_starts) if not cfg.dry_run else 0
            self.act_mode = _act_mode(cfg)
            self.act_timeout_s = _act_timeout(cfg)
            # worker mode steps the shared pad-invariant act core locally;
            # inference mode ships (obs, base key) and the learner-side
            # service steps the SAME core — identical row math either way
            self._core = (
                None
                if self.act_mode == "inference"
                else build_act_core(
                    "sac", cfg, self.envs.single_observation_space, self.action_space
                )
            )
            self._act_params: Any = None
            self._episode_stats = episode_stats
            self._flatten = flatten_obs
            self.key = jax.random.PRNGKey(int(cfg.seed) + 977 * (worker_id + 1))
            self.params: Any = None
            obs, _ = self.envs.reset(seed=_slice_seed(cfg, worker_id, self.epw))
            self.obs_vec = flatten_obs(obs, self.mlp_keys, self.epw)
            self.lifetime = 0

        def set_params(self, params_np: Any, version: int) -> None:
            self.params = params_np
            if self._core is not None:
                self._act_params = self._core.extract_params(params_np)

        def step(self, sink: Any) -> Tuple[int, None]:
            import jax

            epw = self.epw
            # global-step estimate at round granularity: every worker is at
            # the same per-slice count when rounds are full-strength
            if self.params is None or self.lifetime * self.num_workers <= self.learning_starts:
                env_actions = np.stack([self.action_space.sample() for _ in range(epw)])
            elif self.act_mode == "inference":
                self.key, k = jax.random.split(self.key)
                resp = _remote_act(
                    self, {"n": epw, "obs": self.obs_vec, "key": np.asarray(k)}
                )
                env_actions = np.asarray(resp["actions"]).reshape(epw, self.act_dim)
            else:
                self.key, k = jax.random.split(self.key)
                env_actions = np.asarray(
                    self._core.act(self._act_params, self.obs_vec, row_keys(k, epw))[0]
                ).reshape(epw, self.act_dim)
            next_obs, rewards, terminated, truncated, info = self.envs.step(env_actions)
            self.lifetime += epw

            real_next = self._flatten(next_obs, self.mlp_keys, epw).copy()
            if "final_obs" in info:
                for i, fo in enumerate(info["final_obs"]):
                    if fo is not None:
                        real_next[i] = np.concatenate(
                            [np.asarray(fo[k], np.float32).reshape(-1) for k in self.mlp_keys]
                        )
            step_data = {
                "observations": self.obs_vec.reshape(1, epw, -1),
                "next_observations": real_next.reshape(1, epw, -1),
                "actions": env_actions.reshape(1, epw, self.act_dim).astype(np.float32),
                "rewards": np.asarray(rewards, np.float32).reshape(1, epw, 1),
                "terminated": np.asarray(terminated, np.float32).reshape(1, epw, 1),
                "dones": np.logical_or(terminated, truncated)
                .astype(np.float32)
                .reshape(1, epw, 1),
            }
            sink.add(step_data, validate_args=self.validate)
            self.obs_vec = self._flatten(next_obs, self.mlp_keys, epw)
            for ep_rew, ep_len in self._episode_stats(info):
                sink.stat("Rewards/rew_avg", ep_rew)
                sink.stat("Game/ep_len_avg", ep_len)
            return epw, None

    return _SacProgram()


# ---------------------------------------------------------------------------
# DreamerV3 — one vector step per packet (per-env sequential replay; sliced
# merge: each worker's ops replay against its own global env columns)
# ---------------------------------------------------------------------------
def dreamer_v3_program(cfg: Any, worker_id: int, num_workers: int) -> Any:
    import gymnasium as gym
    import jax

    from ..algos.dreamer_v3.utils import extract_masks, prepare_obs
    from ..utils.env import episode_stats, patch_restarted_envs, vectorize
    from .act_core import build_act_core, row_keys

    class _DreamerProgram:
        sync_params = False

        def __init__(self) -> None:
            num_envs = int(cfg.env.num_envs)
            self.epw = num_envs // int(num_workers)
            self.num_workers = int(num_workers)
            wcfg = _slice_cfg(cfg, self.epw)
            self.envs = vectorize(
                wcfg, _slice_seed(cfg, worker_id, self.epw), 0, None,
                restart_handled_by_loop=True,
            )
            obs_space = self.envs.single_observation_space
            action_space = self.envs.single_action_space
            self.cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
            self.mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
            self.obs_keys = self.cnn_keys + self.mlp_keys
            self.is_continuous = isinstance(action_space, gym.spaces.Box)
            self.is_multidiscrete = isinstance(action_space, gym.spaces.MultiDiscrete)
            if self.is_continuous:
                self.actions_dim = [int(np.prod(action_space.shape))]
            elif self.is_multidiscrete:
                self.actions_dim = [int(n) for n in action_space.nvec]
            else:
                self.actions_dim = [int(action_space.n)]
            self.act_total = int(sum(self.actions_dim))
            self.action_space = action_space
            self.validate = bool(cfg.buffer.validate_args)
            self.learning_starts = int(cfg.algo.learning_starts) if not cfg.dry_run else 0
            self.clip_rewards = bool(cfg.env.clip_rewards)
            self.act_mode = _act_mode(cfg)
            self.act_timeout_s = _act_timeout(cfg)

            # worker mode builds the shared pad-invariant act core (world
            # model + actor on host CPU); inference mode stays light — the
            # learner-side service owns the core AND this worker's (h, z, a)
            # latents, keyed (worker_id, env_slot). The worker only tracks
            # which slots need a latent reset on the next request.
            self._core = (
                None
                if self.act_mode == "inference"
                else build_act_core("dreamer_v3", cfg, obs_space, action_space)
            )
            self._act_params: Any = None
            self._pending_reset = np.ones((self.epw,), bool)
            self._prepare_obs = prepare_obs
            self._extract_masks = extract_masks
            self._episode_stats = episode_stats
            self._patch_restarted = patch_restarted_envs
            self.key = jax.random.PRNGKey(int(cfg.seed) + 977 * (worker_id + 1))
            self.params: Any = None
            self.player_state: Any = None
            self.lifetime = 0

            obs, _ = self.envs.reset(seed=_slice_seed(cfg, worker_id, self.epw))
            self.obs = obs
            epw = self.epw
            sd: Dict[str, np.ndarray] = {}
            for k in self.obs_keys:
                sd[k] = np.asarray(obs[k])[np.newaxis]
            sd["actions"] = np.zeros((1, epw, self.act_total), np.float32)
            sd["rewards"] = np.zeros((1, epw, 1), np.float32)
            sd["terminated"] = np.zeros((1, epw, 1), np.float32)
            sd["truncated"] = np.zeros((1, epw, 1), np.float32)
            sd["is_first"] = np.ones((1, epw, 1), np.float32)
            self.step_data = sd

        def set_params(self, params_np: Any, version: int) -> None:
            self.params = params_np
            if self._core is not None:
                self._act_params = self._core.extract_params(params_np)
                if self.player_state is None:
                    self.player_state = self._core.init_state(self._act_params, self.epw)

        def step(self, sink: Any) -> Tuple[int, None]:
            import jax

            epw = self.epw
            step_data = self.step_data
            if (
                self.params is None
                or self.lifetime * self.num_workers <= self.learning_starts
                or (self.act_mode != "inference" and self.player_state is None)
            ):
                actions_env = np.stack([self.action_space.sample() for _ in range(epw)])
                if self.is_continuous:
                    actions_np = actions_env.reshape(epw, -1).astype(np.float32)
                else:
                    oh = []
                    acts2d = actions_env.reshape(epw, -1)
                    for j, adim in enumerate(self.actions_dim):
                        oh.append(np.eye(adim, dtype=np.float32)[acts2d[:, j]])
                    actions_np = np.concatenate(oh, axis=-1)
            elif self.act_mode == "inference":
                host_obs = self._prepare_obs(self.obs, self.cnn_keys, self.mlp_keys, epw)
                self.key, k = jax.random.split(self.key)
                req: Dict[str, Any] = {"n": epw, "obs": host_obs, "key": np.asarray(k)}
                mask = self._extract_masks(self.obs, epw)
                if mask is not None:
                    req["mask"] = mask
                if self._pending_reset.any():
                    req["reset"] = self._pending_reset.copy()
                resp = _remote_act(self, req)
                # only clear after a successful round trip: an act failure
                # crashes this incarnation, and the respawn must re-init its
                # service-side latents from an all-ones reset mask
                self._pending_reset[:] = False
                actions_np = np.asarray(resp["actions_cat"])
                actions_env = np.asarray(resp["actions"])
                if self.is_continuous:
                    actions_env = actions_env.reshape(epw, -1)
                elif not self.is_multidiscrete:
                    actions_env = actions_env.reshape(epw)
            else:
                host_obs = self._prepare_obs(self.obs, self.cnn_keys, self.mlp_keys, epw)
                self.key, k = jax.random.split(self.key)
                env_actions, actions_cat, self.player_state = self._core.act(
                    self._act_params, host_obs, row_keys(k, epw),
                    state=self.player_state, mask=self._extract_masks(self.obs, epw),
                )
                actions_np = np.asarray(actions_cat)
                actions_env = np.asarray(env_actions)
                if self.is_continuous:
                    actions_env = actions_env.reshape(epw, -1)
                elif not self.is_multidiscrete:
                    actions_env = actions_env.reshape(epw)

            step_data["actions"] = actions_np.reshape(1, epw, -1)
            sink.add(step_data, validate_args=self.validate)

            next_obs, rewards, terminated, truncated, info = self.envs.step(actions_env)
            self.lifetime += epw
            dones = np.logical_or(terminated, truncated)
            for ep_rew, ep_len in self._episode_stats(info):
                sink.stat("Rewards/rew_avg", ep_rew)
                sink.stat("Game/ep_len_avg", ep_len)

            real_next_obs = {k: np.asarray(next_obs[k]).copy() for k in self.obs_keys}
            if "final_obs" in info:
                for i, fo in enumerate(info["final_obs"]):
                    if fo is not None:
                        for k in self.obs_keys:
                            real_next_obs[k][i] = np.asarray(fo[k])

            for k in self.obs_keys:
                step_data[k] = np.asarray(next_obs[k])[np.newaxis]
            step_data["is_first"] = np.zeros((1, epw, 1), np.float32)
            step_data["terminated"] = np.asarray(terminated, np.float32).reshape(1, epw, 1)
            step_data["truncated"] = np.asarray(truncated, np.float32).reshape(1, epw, 1)
            rew = np.asarray(rewards, np.float32).reshape(1, epw, 1)
            step_data["rewards"] = np.tanh(rew) if self.clip_rewards else rew

            restarted = self._patch_restarted(info, dones, sink, step_data)
            if restarted is not None:
                if self.act_mode == "inference":
                    self._pending_reset |= np.asarray(restarted, bool).reshape(epw)
                elif self.player_state is not None:
                    self.player_state = self._core.reset_state(
                        self._act_params, restarted, self.player_state
                    )

            dones_idxes = np.nonzero(dones)[0].tolist()
            if dones_idxes:
                reset_data: Dict[str, np.ndarray] = {}
                for k in self.obs_keys:
                    reset_data[k] = real_next_obs[k][dones_idxes][np.newaxis]
                reset_data["terminated"] = step_data["terminated"][:, dones_idxes]
                reset_data["truncated"] = step_data["truncated"][:, dones_idxes]
                reset_data["actions"] = np.zeros((1, len(dones_idxes), self.act_total), np.float32)
                reset_data["rewards"] = step_data["rewards"][:, dones_idxes]
                reset_data["is_first"] = np.zeros_like(reset_data["terminated"])
                sink.add(reset_data, dones_idxes, validate_args=self.validate)
                step_data["rewards"][:, dones_idxes] = 0
                step_data["terminated"][:, dones_idxes] = 0
                step_data["truncated"][:, dones_idxes] = 0
                step_data["is_first"][:, dones_idxes] = 1
                if self.act_mode == "inference":
                    self._pending_reset[dones_idxes] = True
                elif self.player_state is not None:
                    mask = np.zeros((epw,), bool)
                    mask[dones_idxes] = True
                    self.player_state = self._core.reset_state(
                        self._act_params, mask, self.player_state
                    )

            self.obs = next_obs
            return epw, None

    return _DreamerProgram()


# ---------------------------------------------------------------------------
# PPO — one ROLLOUT per packet, strictly one rollout per publication
# ---------------------------------------------------------------------------
def ppo_program(cfg: Any, worker_id: int, num_workers: int) -> Any:
    import gymnasium as gym
    import jax

    from ..algos.ppo.agent import build_agent
    from ..algos.ppo.ppo import make_act_fn, make_value_fn
    from ..algos.ppo.utils import prepare_obs
    from ..parallel.mesh import Distributed
    from ..utils.env import episode_stats, vectorize

    class _PpoProgram:
        sync_params = True  # exactly one rollout per param publication

        def __init__(self) -> None:
            num_envs = int(cfg.env.num_envs)
            self.epw = num_envs // int(num_workers)
            wcfg = _slice_cfg(cfg, self.epw)
            self.envs = vectorize(wcfg, _slice_seed(cfg, worker_id, self.epw), 0, None)
            obs_space = self.envs.single_observation_space
            self.action_space = self.envs.single_action_space
            self.cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
            self.mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
            self.obs_keys = self.cnn_keys + self.mlp_keys
            self.obs_space = obs_space
            self.rollout_steps = int(cfg.algo.rollout_steps)
            self.gamma = float(cfg.algo.gamma)
            self.validate = bool(cfg.buffer.validate_args)
            dist = Distributed(devices=1, accelerator="cpu")
            module, _params = build_agent(
                dist, cfg, obs_space, self.action_space, jax.random.PRNGKey(0), None
            )
            self.module = module
            self._act = make_act_fn(module)
            self._value = make_value_fn(module)
            self._prepare_obs = prepare_obs
            self._episode_stats = episode_stats
            self.key = jax.random.PRNGKey(int(cfg.seed) + 977 * (worker_id + 1))
            self.params: Any = None
            obs, _ = self.envs.reset(seed=_slice_seed(cfg, worker_id, self.epw))
            self.obs = obs

        def set_params(self, params_np: Any, version: int) -> None:
            self.params = params_np

        def step(self, sink: Any) -> Tuple[int, Any]:
            import jax

            epw = self.epw
            rows: Dict[str, List[np.ndarray]] = {}
            ep_stats: List[Tuple[float, float]] = []
            # one slice = a whole rollout: pulse the worker heartbeat between
            # env steps so a slow rollout is never mistaken for a hang
            beat = getattr(self, "beat", None) or (lambda: None)
            for _ in range(self.rollout_steps):
                beat()
                device_obs = self._prepare_obs(self.obs, self.cnn_keys, self.mlp_keys, epw)
                self.key, act_key = jax.random.split(self.key)
                actions, logprobs, values = self._act(self.params, device_obs, act_key)
                np_actions = np.asarray(actions)
                if self.module.is_continuous:
                    env_actions = np_actions.reshape(epw, -1)
                elif isinstance(self.action_space, gym.spaces.MultiDiscrete):
                    env_actions = np_actions.reshape(epw, -1)
                else:
                    env_actions = np_actions.reshape(epw)
                next_obs, rewards, terminated, truncated, info = self.envs.step(env_actions)

                rewards = np.asarray(rewards, np.float32).reshape(epw, 1)
                dones = np.logical_or(terminated, truncated).astype(np.float32).reshape(epw, 1)
                if np.any(truncated) and "final_obs" in info:
                    final_obs = info["final_obs"]
                    trunc_idx = np.nonzero(truncated)[0]
                    stacked = {
                        k: np.stack([np.asarray(final_obs[i][k]) for i in trunc_idx])
                        for k in self.obs_keys
                    }
                    vals = np.asarray(
                        self._value(
                            self.params,
                            self._prepare_obs(stacked, self.cnn_keys, self.mlp_keys, len(trunc_idx)),
                        )
                    )
                    rewards[trunc_idx] += self.gamma * vals.reshape(-1, 1)

                step_data: Dict[str, np.ndarray] = {}
                for k in self.obs_keys:
                    step_data[f"obs:{k}"] = np.asarray(self.obs[k]).reshape(
                        1, epw, *self.obs_space[k].shape
                    )
                step_data["actions"] = np_actions.reshape(1, epw, -1).astype(np.float32)
                step_data["logprobs"] = np.asarray(logprobs).reshape(1, epw, 1)
                step_data["values"] = np.asarray(values).reshape(1, epw, 1)
                step_data["rewards"] = rewards.reshape(1, epw, 1)
                step_data["dones"] = dones.reshape(1, epw, 1)
                for k, v in step_data.items():
                    rows.setdefault(k, []).append(v)
                self.obs = next_obs
                ep_stats.extend(self._episode_stats(info))
            local = {k: np.concatenate(v, axis=0) for k, v in rows.items()}
            next_value = np.asarray(
                self._value(
                    self.params, self._prepare_obs(self.obs, self.cnn_keys, self.mlp_keys, epw)
                )
            )
            return self.rollout_steps * epw, (local, next_value, ep_stats)

    return _PpoProgram()


def merge_ppo_round(rnd: Any, num_workers: int) -> Tuple[Dict[str, np.ndarray], np.ndarray, List[Any]]:
    """Learner-side merge of one PPO fleet round into the full-width
    ``[T, num_envs, ...]`` rollout (+ bootstrap values). Quarantined slots
    are backfilled by duplicating surviving workers' slices — shapes (and
    the jitted update) never change; their episode stats are not
    double-counted."""
    by = {p.worker_id: p.payload for p in rnd.packets}
    present = sorted(by)
    locals_: List[Dict[str, np.ndarray]] = []
    next_vals: List[np.ndarray] = []
    ep_stats: List[Any] = []
    for slot in range(int(num_workers)):
        src = by[slot] if slot in by else by[present[slot % len(present)]]
        locals_.append(src[0])
        next_vals.append(np.asarray(src[1]).reshape(-1, 1))
        if slot in by:
            ep_stats.extend(src[2])
    local = {k: np.concatenate([l[k] for l in locals_], axis=1) for k in locals_[0]}
    next_value = np.concatenate(next_vals, axis=0)
    return local, next_value, ep_stats
