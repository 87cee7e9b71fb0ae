"""DreamerV3 train-step throughput benchmark (the flagship workload).

Times the full jitted DreamerV3-S gradient step — world-model scan over a
[seq 64, batch 16] Atari-shaped batch, imagination horizon 15, actor/critic
updates, Moments, target EMA — on the attached accelerator with synthetic
data (ale-py is not installed; the dummy batch has exactly the MsPacman
shapes, so the XLA program is identical to the real recipe's).

Derived metric: with the Atari-100K recipe's replay_ratio=1, one gradient
step is taken per policy step, so sustained env-steps/sec/chip ≈ gradient
steps/sec (train dominates; the reference's 14 h for 100K policy steps on an
RTX 3080 ⇒ 1.98 steps/s, BASELINE.md MsPacman row).
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_STEPS_PER_SEC = 100_000 / (14 * 3600)  # reference README.md:45-51

BATCH = 16
SEQ = 64
N_ACTIONS = 9  # MsPacman

# The peak-FLOPs table and MFU math live in the library now
# (sheeprl_tpu.telemetry.throughput) so train loops and this bench share one
# implementation; see peak_flops_record / flops_of_lowered / mfu there.


def require_accelerator() -> str:
    """What a bench leg measures on, settled before anything else touches
    JAX: the attached accelerator's platform, or `cpu-forced` when the
    operator set BENCH_FORCE_CPU=1. A leg that finds no accelerator exits
    non-zero: a CPU timing under the name of a device metric is worse than
    no number."""
    if os.environ.get("BENCH_FORCE_CPU"):
        from sheeprl_tpu.utils.virtual_mesh import force_virtual_cpu_mesh

        force_virtual_cpu_mesh(1)
        return "cpu-forced"
    import jax

    platform = jax.devices()[0].platform
    if platform == "cpu":
        print(
            "bench: JAX found no accelerator (platform cpu); nothing is measured. "
            "BENCH_FORCE_CPU=1 measures this host instead, labelled as such.",
            file=sys.stderr,
        )
        raise SystemExit(3)
    return platform


def record() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.config import compose
    from sheeprl_tpu.config.container import Config
    from sheeprl_tpu.optim import clipped
    from sheeprl_tpu.config import instantiate
    from sheeprl_tpu.parallel import build_distributed
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_train_fn
    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
    import gymnasium as gym

    # BENCH_DV3_SIZE (debugging only): swap the S preset for XS etc. and
    # scale the batch down so the plumbing can be exercised on a laptop CPU
    size = os.environ.get("BENCH_DV3_SIZE", "")
    batch = int(os.environ.get("BENCH_DV3_BATCH", BATCH))
    seq = int(os.environ.get("BENCH_DV3_SEQ", SEQ))
    # BENCH_DV3_PRECISION=bf16-mixed measures the MXU's native reduced
    # precision (the production recipe default stays f32 for baseline parity)
    precision = os.environ.get("BENCH_DV3_PRECISION", "")
    cfg = compose(
        "config",
        ["exp=dreamer_v3_100k_ms_pacman"]
        + ([f"algo=dreamer_v3_{size}"] if size else [])
        + ([f"fabric.precision={precision}"] if precision else [])
        + [
            "env=dummy",
            "env.id=discrete_dummy",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
            f"algo.per_rank_batch_size={batch}",
            f"algo.per_rank_sequence_length={seq}",
        ],
    )
    dist = build_distributed(cfg)
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8)})
    actions_dim = [N_ACTIONS]
    key = jax.random.key(0)
    wm, actor, critic, params = build_agent(dist, cfg, obs_space, actions_dim, False, key)
    txs = {
        "wm": clipped(instantiate(cfg.algo.world_model.optimizer), cfg.algo.world_model.clip_gradients),
        "actor": clipped(instantiate(cfg.algo.actor.optimizer), cfg.algo.actor.clip_gradients),
        "critic": clipped(instantiate(cfg.algo.critic.optimizer), cfg.algo.critic.clip_gradients),
    }
    opt_states = {
        "wm": txs["wm"].init(params["wm"]),
        "actor": txs["actor"].init(params["actor"]),
        "critic": txs["critic"].init(params["critic"]),
        "step": jnp.zeros((), jnp.int32),
    }
    moments = init_moments()
    train = make_train_fn(wm, actor, critic, txs, cfg, False, actions_dim)

    rng = np.random.default_rng(0)
    host_data = {
        "rgb": rng.integers(0, 255, (1, seq, batch, 64, 64, 3)).astype(np.uint8),
        "actions": np.eye(N_ACTIONS, dtype=np.float32)[rng.integers(0, N_ACTIONS, (1, seq, batch))],
        "rewards": rng.standard_normal((1, seq, batch, 1)).astype(np.float32),
        "terminated": np.zeros((1, seq, batch, 1), np.float32),
        "truncated": np.zeros((1, seq, batch, 1), np.float32),
        "is_first": np.zeros((1, seq, batch, 1), np.float32),
    }
    sharding = dist.sharding(None, None, "dp")  # train takes [G, T, B, ...]

    def stage_data() -> dict:
        # a FRESH device batch per call: `train` donates its batch buffers
        # (exactly like the train loop, whose prefetcher hands out fresh
        # arrays every burst); the async device_put overlaps the previous
        # step's compute, same as the loop's staged prefetch
        return {k: jax.device_put(v, sharding) for k, v in host_data.items()}

    data = stage_data()

    from sheeprl_tpu.utils.utils import enable_compilation_cache

    enable_compilation_cache()

    _t_start = time.perf_counter()

    def _phase(msg: str) -> None:
        from sheeprl_tpu.telemetry.sinks import write_event

        write_event(
            {"event": "bench_progress", "msg": msg, "t": round(time.perf_counter() - _t_start, 1)},
            sys.stderr,
        )

    _phase("setup done; lowering for cost_analysis")

    # model FLOPs per gradient step from the compiled program itself
    # (jit(...).lower().compile().cost_analysis(), VERDICT r3 item 1) — the
    # basis for the MFU figure when the chip's peak is known. The extraction
    # (cheap pre-compile estimate, executable fallback) is
    # telemetry.throughput.flops_of_lowered.
    from sheeprl_tpu.telemetry.throughput import flops_of_lowered

    flops_per_step = None
    try:
        tkey0 = jax.random.key(1)
        lowered = train.lower(params, opt_states, moments, data, jax.random.split(tkey0, 1))
        flops_per_step = flops_of_lowered(lowered)  # one call == one grad step (G=1)
    except Exception as err:  # cost_analysis is best-effort on some backends
        print(f"[bench] cost_analysis unavailable: {err}", file=sys.stderr)

    _phase(f"cost_analysis done (flops={flops_per_step}); compiling + warmup")
    tkey = jax.random.key(1)
    # compile + settle; the per-step warmup time picks the cap granularity
    _t_warm = time.perf_counter()
    for _ in range(3):
        tkey, k = jax.random.split(tkey)
        params, opt_states, moments, metrics = train(
            params, opt_states, moments, data, jax.random.split(k, 1)
        )
        data = stage_data()
    jax.block_until_ready(metrics)
    _phase(f"warmup done in {time.perf_counter() - _t_warm:.1f}s (incl. any compile); probing")
    # one timed step AFTER warmup (compile already paid) classifies the
    # host speed for the sync granularity below — averaging the compile in
    # would misread a fast chip with a cold cache as a slow host
    _t_probe = time.perf_counter()
    tkey, k = jax.random.split(tkey)
    params, opt_states, moments, metrics = train(
        params, opt_states, moments, data, jax.random.split(k, 1)
    )
    jax.block_until_ready(metrics)
    warm_step_s = time.perf_counter() - _t_probe
    data = stage_data()
    _phase(f"probe step {warm_step_s:.2f}s; timing")

    # time-capped: on a slow machine stop early and report SPS over the
    # reps that ran, instead of being killed by the subprocess budget. The
    # cap also shrinks to whatever remains of the SUBPROCESS budget
    # (BENCH_STEP_BUDGET_S) after setup/compile — a cold compile must
    # degrade to a few-rep measurement, not a budget kill with no record.
    max_reps = 20
    cap_s = float(os.environ.get("BENCH_STEP_WALL_S", 240))
    deadline = os.environ.get("BENCH_STEP_DEADLINE")
    if deadline:
        # absolute wall-clock deadline set by the parent at SPAWN time, so
        # pre-setup costs (imports, config, build) are accounted exactly;
        # 45 s tail covers one in-flight step past the cap check + the
        # final sync and record print
        cap_s = max(10.0, min(cap_s, float(deadline) - time.time() - 45.0))
    # dispatch is async, so the wall check must SYNC first or it never
    # fires. Granularity is adaptive: a slow host (seconds per step) syncs
    # every rep — pipelining is irrelevant there and a coarser check would
    # blow straight past the budget; a fast chip keeps the 5-rep pipeline.
    sync_every = 1 if warm_step_s > 1.0 else 5
    reps = 0
    t0 = time.perf_counter()
    while reps < max_reps:
        tkey, k = jax.random.split(tkey)
        params, opt_states, moments, metrics = train(
            params, opt_states, moments, data, jax.random.split(k, 1)
        )
        data = stage_data()  # dispatch overlaps the in-flight step's compute
        reps += 1
        if reps % sync_every == 0 or reps == max_reps:
            jax.block_until_ready(metrics)
            if time.perf_counter() - t0 > cap_s:
                break
    jax.block_until_ready(metrics)
    elapsed = time.perf_counter() - t0
    sps = reps / elapsed
    rec = {
        "metric": "DreamerV3-S Atari-shape gradient steps/sec/chip "
        "(≈ env-steps/sec at replay_ratio 1; baseline: MsPacman-100K 14h on RTX 3080)",
        "value": round(sps, 3),
        "unit": "steps/s",
        "vs_baseline": round(sps / BASELINE_STEPS_PER_SEC, 3),
        "platform": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", ""),
        "precision": str(cfg.fabric.precision),
    }
    # the basis label is stamped UNCONDITIONALLY (vendor table / measured
    # host matmul / unknown): every record names its MFU denominator class
    # even when cost analysis yielded no model FLOPs and mfu is omitted —
    # the measurement itself only runs when there are FLOPs to divide
    from sheeprl_tpu.telemetry.throughput import mfu as _mfu
    from sheeprl_tpu.telemetry.throughput import peak_flops_basis_for, peak_flops_record

    rec["peak_flops_basis"] = peak_flops_basis_for(jax.devices()[0])
    if flops_per_step is not None:
        rec["model_flops_per_step"] = flops_per_step
        peak = peak_flops_record(jax.devices()[0])["peak_flops"]
        if peak is not None:
            # flops_per_step and sps are whole-mesh quantities; normalize the
            # peak by the device count so multi-chip runs report true MFU
            n_dev = jax.device_count()
            rec["mfu"] = round(_mfu(flops_per_step, sps, peak, n_dev), 4)
            rec["peak_flops_assumed"] = peak
            rec["devices"] = n_dev
    # memory high-waters of the bench process (informational, never gated):
    # kernel VmHWM for the host, allocator peak_bytes_in_use for the device
    try:
        from sheeprl_tpu.telemetry.memory import host_rss_peak_bytes
        from sheeprl_tpu.telemetry.xla import device_memory_stats

        peak_rss = host_rss_peak_bytes()
        if peak_rss:
            rec["peak_rss_bytes"] = int(peak_rss)
        dev_stats = device_memory_stats()
        if dev_stats.get("peak_bytes_in_use"):
            rec["device_peak_bytes"] = int(dev_stats["peak_bytes_in_use"])
    except Exception:
        pass
    return rec


def main() -> None:
    # one schema-validated JSONL line on stdout (shared with in-run telemetry)
    from sheeprl_tpu.telemetry.sinks import write_event

    platform = require_accelerator()
    write_event({"event": "bench", **record(), "platform": platform}, sys.stdout)


if __name__ == "__main__":
    main()
