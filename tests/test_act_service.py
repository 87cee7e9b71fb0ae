"""Sebulba batched acting (fleet/act_core.py + fleet/act_service.py) and
Anakin fused acting (fleet/anakin.py).

The contract under test is PARITY: moving the policy step off the worker
hosts onto the learner-hosted batched service must not move the numbers.

* SAC: a coalesced, power-of-two-padded service batch returns each
  worker's rows bitwise-identical to the same act core stepping those rows
  with the same per-row keys AT THE SAME BUCKET WIDTH (see `_act_at_width`
  for why the width is part of the contract), and equal to the worker's
  own narrower call within float32 rounding;
* DV3: same, with the (h, z, a) latents living service-side — session
  carry across steps, reset-mask re-initialization, and the idempotent
  retry path (a re-sent request answers from cache WITHOUT re-stepping
  latents) all stay bitwise-equal to the player core at the same width;
* e2e: a 2-worker SAC fleet run under ``fleet.act_mode=inference``
  produces a replay buffer BITWISE-IDENTICAL to the worker-hosted run's —
  the acceptance statement of the Sebulba refactor;
* the batcher never coalesces across the mask-presence boundary or past
  the widest bucket;
* doctor: the ``act_service_starvation`` finding fires on mostly-empty
  buckets + act_submit-bound workers, and stays quiet otherwise;
* Anakin: the fused vmap+scan chunk advances slots*chunk env steps per
  device call, deterministically.
"""
import numpy as np
import pytest

from sheeprl_tpu.config import Config
from sheeprl_tpu.fleet.act_service import ActService, _ActJob


def _svc(program="sac", buckets=(1, 2, 4, 8)):
    cfg = Config({"fleet": {"act": {"buckets": list(buckets), "max_wait_ms": 1.0}}})
    return ActService(cfg, program)


# ---------------------------------------------------------------------------
# unit: batch formation (no core needed — _take_batch_locked is pure queue)
# ---------------------------------------------------------------------------
def test_take_batch_respects_width_and_mask_boundaries():
    svc = _svc()
    drop = lambda r: None

    def job(n, mask=None):
        req = {"n": n}
        if mask is not None:
            req["mask"] = mask
        return _ActJob(req, drop)

    # widest bucket is 8: 3 + 3 fit, the 4-row request starts the next batch
    svc._pending.extend([job(3), job(3), job(4)])
    first = svc._take_batch_locked()
    assert [j.req["n"] for j in first] == [3, 3]
    assert [j.req["n"] for j in svc._take_batch_locked()] == [4]

    # with/without an action mask never coalesce (different jitted variants)
    m = {"head0": np.ones((2, 3), bool)}
    svc._pending.extend([job(2), job(2, mask=m)])
    assert [j.req.get("mask") is None for j in svc._take_batch_locked()] == [True]
    assert [j.req.get("mask") is None for j in svc._take_batch_locked()] == [False]

    # a request wider than every bucket rides alone, padded to its own pow-2
    assert svc._bucket(11) == 16


def _act_at_width(core, params, obs, keys, n, width, state=None):
    """`core.act` on ``n`` rows padded to ``width`` the way the service pads
    (zero obs/key rows, init-state rows), sliced back to ``n``.

    The bitwise contract is: same rows, same keys, same bucket width -> same
    bits. It is NOT "whatever the width": a row's value depends only on
    (params, obs[i], key[i], state[i]) — the vmap guarantees that — but XLA
    vectorises elementwise math differently at different batch widths, so
    the LAST BIT of a float can move with the width (jaxlib 0.9.0 CPU: SAC's
    tanh gives -0.9983063 at width 8 and -0.9983062 at width 3; a TPU tiles
    its matmuls by width too). Comparing against the core at another width
    with array_equal would test the compiler, not the service, so the
    width-independent comparison below uses a float32 rounding tolerance
    instead, and the bitwise one runs the reference at the service's width."""
    import jax

    from sheeprl_tpu.fleet.act_service import _concat_rows, _pad_rows

    keys = np.asarray(jax.device_get(keys))
    keys = np.concatenate([keys, np.zeros((width - n,) + keys.shape[1:], keys.dtype)], axis=0)
    if state is not None and width > n:
        init_row = jax.tree.map(np.asarray, core.init_state(params, 1))
        state = _concat_rows([jax.tree.map(np.asarray, state)] + [init_row] * (width - n))
    actions, cat, new_state = core.act(params, _pad_rows(obs, n, width), keys, state=state)
    cut = lambda x: None if x is None else np.asarray(x)[:n]
    return cut(actions), cut(cat), (
        None if new_state is None else jax.tree.map(cut, new_state)
    )


# one ulp of float32 near 1.0 is 6e-8 (1.2e-7 above it): two widths of the
# same program may differ by a few of them after a matmul and a tanh
_WIDTH_RTOL, _WIDTH_ATOL = 1e-5, 1e-6


# ---------------------------------------------------------------------------
# SAC: coalesced + padded service batch == per-worker local core act, bitwise
# ---------------------------------------------------------------------------
def _sac_core_and_params(obs_dim=5, act_dim=3, hidden=8):
    import gymnasium as gym
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.algos.sac.agent import SACActor
    from sheeprl_tpu.fleet.act_core import build_act_core

    cfg = Config({"algo": {"actor": {"hidden_size": hidden}}})
    space = gym.spaces.Box(-1.0, 1.0, (act_dim,), np.float32)
    core = build_act_core("sac", cfg, None, space)
    actor = SACActor(
        action_dim=act_dim,
        hidden_size=hidden,
        action_low=space.low.tolist(),
        action_high=space.high.tolist(),
    )
    variables = actor.init(jax.random.PRNGKey(0), jnp.zeros((1, obs_dim)))
    params_np = {"actor": jax.tree.map(np.asarray, variables["params"])}
    return core, params_np


def test_sac_service_batch_bitwise_matches_worker_core():
    import jax

    from sheeprl_tpu.fleet.act_core import row_keys

    core, params_np = _sac_core_and_params()
    svc = _svc("sac")
    svc.core = core
    svc.swap_params(params_np, version=5)

    rng = np.random.default_rng(0)
    layout = {0: 3, 1: 2}  # two workers coalesce to 5 rows -> bucket 8 (3 pad)
    obs = {w: rng.standard_normal((n, 5)).astype(np.float32) for w, n in layout.items()}
    keys = {w: np.asarray(jax.random.PRNGKey(10 + w)) for w in layout}
    replies = {}
    jobs = [
        _ActJob(
            {"worker_id": w, "incarnation": 0, "req_id": 1, "n": n,
             "obs": obs[w], "key": keys[w]},
            lambda r, w=w: replies.__setitem__(w, r),
        )
        for w, n in layout.items()
    ]
    svc._run_batch(jobs)

    host = core.extract_params(params_np)  # the worker-mode program's params
    for w, n in layout.items():
        assert replies[w]["version"] == 5
        # bitwise at the service's width: coalescing with another worker's
        # rows and the row's position in the bucket change nothing
        ref8, _, _ = _act_at_width(core, host, obs[w], row_keys(keys[w], n), n, 8)
        assert np.array_equal(replies[w]["actions"], ref8), (
            "service actions diverged from the same core at the same bucket width"
        )
        # the worker-hosted call at its own width: same value to rounding
        ref, _, _ = core.act(host, obs[w], row_keys(keys[w], n))
        np.testing.assert_allclose(
            replies[w]["actions"], np.asarray(ref), rtol=_WIDTH_RTOL, atol=_WIDTH_ATOL
        )

    # exact-width batch (4 rows -> bucket 4, no padding) is ALSO bitwise equal
    obs4 = rng.standard_normal((4, 5)).astype(np.float32)
    key4 = np.asarray(jax.random.PRNGKey(99))
    svc._run_batch([
        _ActJob(
            {"worker_id": 2, "incarnation": 0, "req_id": 1, "n": 4,
             "obs": obs4, "key": key4},
            lambda r: replies.__setitem__(2, r),
        )
    ])
    ref4, _, _ = core.act(host, obs4, row_keys(key4, 4))
    assert np.array_equal(replies[2]["actions"], np.asarray(ref4))

    # observability: occupancy + pad-waste recorded, engine-facing snapshot
    snap = svc.snapshot()
    assert snap["act_batches"] == 2 and snap["act_requests"] == 0  # direct _run_batch
    assert 0.0 < snap["act_occupancy"] <= 1.0
    assert snap["act_pad_waste"] > 0.0  # the 5-in-8 batch wasted 3 rows
    assert snap["act_version"] == 5


# ---------------------------------------------------------------------------
# DV3: service-side latents — carry, resets, respawn rehydration, idempotency
# ---------------------------------------------------------------------------
DV3_ARGS = [
    "exp=dreamer_v3",
    "env=dummy",
    "env.id=discrete_dummy",
    "env.num_envs=1",
    "env.sync_env=True",
    "env.capture_video=False",
    "algo=dreamer_v3_XS",
    "algo.dense_units=16",
    "algo.mlp_layers=1",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=16",
    "algo.world_model.transition_model.hidden_size=16",
    "algo.world_model.representation_model.hidden_size=16",
    "algo.world_model.discrete_size=4",
    "algo.world_model.stochastic_size=4",
    "algo.cnn_keys.encoder=[rgb]",
    "algo.mlp_keys.encoder=[state]",
    "buffer.memmap=False",
    "metric.log_level=0",
]


def _state_rows_equal(svc, wid, ref_state, n):
    import jax

    for slot in range(n):
        row = svc.sessions.get(f"{wid}/{slot}")
        assert row is not None
        got = jax.tree.leaves(row)
        want = jax.tree.leaves(jax.tree.map(lambda x: np.asarray(x)[slot : slot + 1], ref_state))
        assert all(np.array_equal(g, w) for g, w in zip(got, want)), (
            f"session latent for {wid}/{slot} diverged from the worker-hosted player"
        )


def test_dv3_service_sessions_resets_and_idempotency():
    import jax

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.fleet.act_core import build_act_core, row_keys
    from sheeprl_tpu.serve.builders import _HostDist
    from sheeprl_tpu.utils.env import vectorize

    cfg = compose("config", DV3_ARGS)
    env = vectorize(cfg, cfg.seed, 0).envs[0]
    obs_space, act_space = env.observation_space, env.action_space
    env.close()
    _wm, _actor, _critic, params = build_agent(
        _HostDist(), cfg, obs_space, [int(act_space.n)], False, jax.random.key(0)
    )
    params_np = jax.tree.map(np.asarray, params)
    core = build_act_core("dreamer_v3", cfg, obs_space, act_space)
    host = core.extract_params(params_np)

    svc = _svc("dreamer_v3")
    svc.core = core
    svc.swap_params(params_np, version=1)

    rng = np.random.default_rng(7)

    def obs_of(n):
        return {
            "rgb": rng.integers(0, 255, (n, *obs_space["rgb"].shape), np.uint8),
            "state": rng.standard_normal(
                (n, int(np.prod(obs_space["state"].shape)))
            ).astype(np.float32),
        }

    replies = {}

    def send(wid, n, key, obs, req_id, reset=None):
        req = {"worker_id": wid, "incarnation": 0, "req_id": req_id, "n": n,
               "obs": obs, "key": np.asarray(key)}
        if reset is not None:
            req["reset"] = np.asarray(reset, bool)
        return _ActJob(req, lambda r, w=wid: replies.__setitem__(w, r))

    # -- step 1: two workers coalesce (2 + 1 -> bucket 4, stateful padding);
    # both ship the respawn convention's full reset mask
    o0, o1 = obs_of(2), obs_of(1)
    k0, k1 = jax.random.PRNGKey(20), jax.random.PRNGKey(21)
    svc._run_batch([
        send(0, 2, k0, o0, 1, reset=[True, True]),
        send(1, 1, k1, o1, 1, reset=[True]),
    ])
    # reference at the service's bucket width (4): see _act_at_width
    ref0_a, ref0_cat, ref0_st = _act_at_width(
        core, host, o0, row_keys(np.asarray(k0), 2), 2, 4, state=core.init_state(host, 2)
    )
    ref1_a, _, ref1_st = _act_at_width(
        core, host, o1, row_keys(np.asarray(k1), 1), 1, 4, state=core.init_state(host, 1)
    )
    assert np.array_equal(replies[0]["actions"], ref0_a)
    assert np.array_equal(replies[0]["actions_cat"], ref0_cat)
    assert np.array_equal(replies[1]["actions"], ref1_a)
    _state_rows_equal(svc, 0, ref0_st, 2)
    _state_rows_equal(svc, 1, ref1_st, 1)
    # ... and the worker-hosted player's own width-2 call: same latents to
    # float32 rounding (the discrete actions are then the same draw)
    _, _, narrow_st = core.act(
        host, o0, row_keys(np.asarray(k0), 2), state=core.init_state(host, 2)
    )
    for got, want in zip(jax.tree.leaves(ref0_st), jax.tree.leaves(narrow_st)):
        np.testing.assert_allclose(got, np.asarray(want), rtol=_WIDTH_RTOL, atol=_WIDTH_ATOL)

    # -- step 2: worker 0 again, no reset — the service must act from the
    # latents it stored, exactly like the worker-hosted player's carry
    o0b = obs_of(2)
    k0b = jax.random.PRNGKey(22)
    svc._run_batch([send(0, 2, k0b, o0b, 2)])
    ref0b_a, _, ref0b_st = core.act(host, o0b, row_keys(np.asarray(k0b), 2), state=ref0_st)
    assert np.array_equal(replies[0]["actions"], np.asarray(ref0b_a))
    _state_rows_equal(svc, 0, ref0b_st, 2)

    # -- step 3: slot 0 done -> per-row reset mask, worker-mode twin is
    # reset_state on the carried latents
    o0c = obs_of(2)
    k0c = jax.random.PRNGKey(23)
    svc._run_batch([send(0, 2, k0c, o0c, 3, reset=[True, False])])
    st_reset = core.reset_state(host, np.array([True, False]), ref0b_st)
    ref0c_a, _, ref0c_st = core.act(host, o0c, row_keys(np.asarray(k0c), 2), state=st_reset)
    assert np.array_equal(replies[0]["actions"], np.asarray(ref0c_a))
    _state_rows_equal(svc, 0, ref0c_st, 2)

    # -- idempotent retry: a re-sent req_id answers from the cache without
    # re-stepping latents (junk obs would change the answer if it recomputed)
    cached = replies[0]
    retries = []
    svc.submit(
        {"worker_id": 0, "incarnation": 0, "req_id": 3, "n": 2,
         "obs": obs_of(2), "key": np.asarray(k0c)},
        retries.append,
    )
    assert len(retries) == 1 and retries[0] is cached
    assert svc.queue_depth == 0  # never enqueued
    _state_rows_equal(svc, 0, ref0c_st, 2)  # latents untouched

    # a DIFFERENT req_id is new work, not a cache hit
    svc.submit(
        {"worker_id": 0, "incarnation": 0, "req_id": 4, "n": 2,
         "obs": obs_of(2), "key": np.asarray(k0c)},
        retries.append,
    )
    assert svc.queue_depth == 1


# ---------------------------------------------------------------------------
# e2e: 2-worker SAC fleet, inference vs worker acting — buffers bitwise equal
# ---------------------------------------------------------------------------
def _sac_args(run_name, total=256, extra=()):
    return [
        "exp=sac",
        "env=dummy",
        "env.id=continuous_dummy",
        "env.num_envs=2",
        "env.sync_env=True",
        "env.capture_video=False",
        "metric.log_level=1",
        f"algo.total_steps={total}",
        "algo.learning_starts=16",
        "algo.per_rank_batch_size=4",
        "algo.hidden_size=8",
        "algo.mlp_keys.encoder=[state]",
        "algo.run_test=False",
        "algo.fleet.workers=2",
        "buffer.size=4096",
        "buffer.memmap=False",
        "buffer.checkpoint=True",
        "checkpoint.every=0",
        "checkpoint.save_last=True",
        "model_manager.disabled=True",
        "seed=3",
        f"run_name={run_name}",
        "fleet.backoff_s=0.05",
        "fleet.stats_every_s=0.5",
    ] + list(extra)


def _final_ckpt(run_name):
    from pathlib import Path

    from sheeprl_tpu.utils.checkpoint import CheckpointManager

    base = Path("logs/runs/sac/continuous_dummy") / run_name
    cks = sorted(
        (base / "version_0" / "checkpoint").glob("ckpt_*.ckpt"),
        key=lambda p: int(p.stem.split("_")[1]),
    )
    assert cks, f"no checkpoint under {base}"
    return CheckpointManager.load(cks[-1]), base


def test_sac_fleet_inference_mode_matches_worker_mode_ledger_e2e():
    """THE acceptance run: the same 256-step 2-worker SAC fleet, acted once
    through the batched service and once per-worker. The staleness/Ratio
    ledger, grad-step count and buffer fill must be IDENTICAL, and the
    inference run's telemetry must carry the act_* stats and the
    act_submit/act_infer trace stages.

    Per-ACT-CALL bitwise parity (same params/obs/key -> same action) is
    pinned by the unit tests above; whole-run action streams are not
    comparable across modes because worker-mode programs adopt param
    publications asynchronously (stale-but-bounded ctrl-queue drain — a
    timing race even between two worker-mode runs), while the service
    always acts with the newest publication."""
    import json

    from sheeprl_tpu.cli import run

    run(_sac_args("act_e2e_infer", extra=["fleet.act_mode=inference"]))
    run(_sac_args("act_e2e_worker"))
    inf, base = _final_ckpt("act_e2e_infer")
    ref, _ = _final_ckpt("act_e2e_worker")

    assert inf["policy_step"] == ref["policy_step"] == 256
    assert inf["cumulative_grad_steps"] == ref["cumulative_grad_steps"] > 0
    assert inf["ratio"] == ref["ratio"]
    assert inf["rb"]["pos"] == ref["rb"]["pos"]
    assert inf["rb"]["full"] == ref["rb"]["full"]
    a, b = inf["rb"]["buffer"], ref["rb"]["buffer"]
    assert set(a) == set(b)
    for k in a:
        assert a[k].shape == b[k].shape and a[k].dtype == b[k].dtype
    # the random warmup phase (before the first publication) IS bitwise
    # comparable: both modes draw from identically-seeded action spaces
    warmup_rows = 16 // 2  # learning_starts env steps / num_envs per row
    assert np.array_equal(
        a["actions"][:warmup_rows], b["actions"][:warmup_rows]
    ), "pre-publication action rows diverged — env/action-space seeding broke"

    events = [json.loads(ln) for ln in open(base / "version_0" / "telemetry.jsonl")]
    intervals = [
        e for e in events
        if e["event"] == "fleet" and e.get("action") == "interval"
    ]
    assert intervals and intervals[-1].get("act_mode") == "inference"
    assert any((e.get("act_batches") or 0) > 0 for e in intervals)
    stages = {e.get("name") for e in events if e["event"] == "trace_span"}
    assert "act_infer" in stages  # the service's side of the new stage pair
    # the worker's act_submit half lives on each worker's own stream
    worker_streams = sorted((base / "version_0").glob("workers/worker_*/telemetry.jsonl"))
    assert worker_streams
    wstages = {
        e.get("name")
        for p in worker_streams
        for e in map(json.loads, open(p))
        if e.get("event") == "trace_span"
    }
    assert "act_submit" in wstages
    from sheeprl_tpu.telemetry.schema import validate_jsonl

    assert validate_jsonl(base / "version_0" / "telemetry.jsonl") == []


# ---------------------------------------------------------------------------
# doctor: act_service_starvation red/green
# ---------------------------------------------------------------------------
def _starvation_events(occupancy, batches, submit_ms=400.0, other_ms=50.0):
    return [
        {"event": "fleet", "action": "interval", "step": 100,
         "act_batches": batches, "act_occupancy": occupancy,
         "act_pad_waste": 1.0 - occupancy},
        {"event": "trace_span", "role": "worker", "name": "act_submit",
         "dur_ms": submit_ms},
        {"event": "trace_span", "role": "worker", "name": "env_step",
         "dur_ms": other_ms},
        {"event": "trace_span", "role": "learner", "name": "act_infer",
         "dur_ms": submit_ms * 0.9},
    ]


def test_act_service_starvation_doctor_red_green():
    from sheeprl_tpu.diag.findings import detect_act_service_starvation
    from sheeprl_tpu.diag.timeline import Timeline

    red = detect_act_service_starvation(Timeline(_starvation_events(0.2, 30)), None)
    assert len(red) == 1 and red[0].code == "act_service_starvation"
    assert red[0].severity == "warning"
    assert "fleet.act.max_wait_ms" in red[0].remediation
    assert red[0].data["batches"] == 30

    # green: healthy occupancy
    assert not detect_act_service_starvation(Timeline(_starvation_events(0.9, 30)), None)
    # green: too few batches to judge
    assert not detect_act_service_starvation(Timeline(_starvation_events(0.2, 5)), None)
    # green: workers bound elsewhere (env stepping dwarfs act_submit)
    assert not detect_act_service_starvation(
        Timeline(_starvation_events(0.2, 30, submit_ms=50.0, other_ms=800.0)), None
    )
    # green: no act service in the run at all
    assert not detect_act_service_starvation(
        Timeline([{"event": "fleet", "action": "interval", "step": 1}]), None
    )


# ---------------------------------------------------------------------------
# Anakin: fused vmap+scan chunks, deterministic, fleet-program surface
# ---------------------------------------------------------------------------
ANAKIN_CFG = {
    "seed": 1,
    "fleet": {"anakin": {"slots": 16, "chunk": 8, "obs_dim": 4,
                         "act_dim": 2, "hidden": 8, "horizon": 16}},
}


def test_anakin_fused_scan_advances_and_is_deterministic():
    from sheeprl_tpu.fleet.anakin import build_anakin, run_anakin

    out = run_anakin(Config(ANAKIN_CFG), min_steps=2 * 16 * 8)
    assert out["env_steps"] >= 2 * 16 * 8
    assert out["steps_per_s"] > 0
    assert (out["slots"], out["chunk"]) == (16, 8)

    # one jitted call advances every slot chunk steps, reproducibly
    params, carry, scan_fn, slots, chunk = build_anakin(Config(ANAKIN_CFG))
    c1, r1 = scan_fn(params, carry)
    params2, carry2, scan_fn2, _, _ = build_anakin(Config(ANAKIN_CFG))
    c2, r2 = scan_fn2(params2, carry2)
    assert float(r1) == float(r2)
    assert np.array_equal(np.asarray(c1[0]), np.asarray(c2[0]))
    assert int(c1[1][0]) == chunk  # per-slot step counter advanced


def test_anakin_program_steps_and_ignores_foreign_publications():
    from sheeprl_tpu.engine import RecordingSink
    from sheeprl_tpu.fleet.anakin import anakin_program

    prog = anakin_program(Config(ANAKIN_CFG), 0, 1)
    assert prog.sync_params is False
    before = [np.asarray(x) for x in (prog.params["w1"], prog.params["w2"])]
    # a DV3-shaped publication must be ignored, not crash the worker
    prog.set_params({"wm": {"k": np.zeros((3, 3), np.float32)}}, 1)
    assert np.array_equal(np.asarray(prog.params["w1"]), before[0])
    sink = RecordingSink()
    n, payload = prog.step(sink)
    assert n == 16 * 8 and payload is None
    assert sink.stats and sink.stats[0][0] == "Rewards/rew_avg"
