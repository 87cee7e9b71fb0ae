"""Headline benchmark driver. Prints one JSON record per metric, one per
line; the LAST line on stdout is the headline record (the driver parses the
last line):

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

Default (`python bench.py`): two DreamerV3 measurements —

1. compute-only: the full jitted DreamerV3-S gradient step on Atari-shaped
   synthetic batches (bench_dv3.py; baseline MsPacman-100K = 14 h on an
   RTX 3080 ⇒ 1.98 policy-steps/s, README.md:45-51 / BASELINE.md), and
2. end-to-end (headline): the reference's own 16_384-step DreamerV3
   micro-bench recipe (configs/exp/dreamer_v3_benchmarks.yaml — tiny nets,
   replay_ratio 0.0625, 1 env; BASELINE.md 1589.30 s on 4 CPUs), run through
   the real CLI: env stepping + replay buffer + replay prefetch +
   train, with env=dummy standing in for MsPacman (ale-py is not installed;
   the obs/action shapes and therefore the XLA programs are identical).

Contract:
* the parent never touches JAX: each measurement runs in a SUBPROCESS with
  its own wall-clock budget (`BENCH_E2E_BUDGET_S`, default 1100 s;
  `BENCH_STEP_BUDGET_S`, default 420 s), so one process at a time holds the
  chip and a wedged leg cannot hang the whole bench;
* a leg that finds no accelerator exits non-zero and prints no record: a CPU
  timing is never written under the name of a device metric. The one
  exception is the operator's explicit `BENCH_FORCE_CPU=1`, whose records
  are labelled `platform: cpu-forced`, carry the label in their metric name
  and have no `vs_baseline`;
* the end-to-end run additionally caps itself (`algo.max_wall_time_s` =
  `BENCH_E2E_WALL_S`, 950 s): on a slower-than-expected machine it stops at
  a step boundary and reports SPS over the steps that actually ran;
* inside a measurement all training output is redirected to stderr — the
  only thing a subprocess writes to stdout is its one JSON line;
* if the end-to-end leg fails or times out, the compute-only record is
  printed as the headline (with `e2e_error` noting why); if every leg fails
  the last line is an error record and the exit code is non-zero.

Subcommands: `ppo` / `a2c` (reference CartPole wall-clock recipes, 81.27 s /
84.76 s baselines), `sac` (LunarLanderContinuous, 320.21 s baseline),
`dv1` / `dv2` / `dv3` (the reference Dreamer micro-benches, 2207.13 s /
906.42 s / 1589.30 s baselines), `dv3_step` (compute-only only).
`BENCH_RECIPE_WALL_S` wall-caps the ppo/a2c/sac legs.
`BENCH_DREAMER_STEPS` overrides the 16_384-step count (debugging only — the
recorded `vs_baseline` stays an SPS ratio either way).
"""
from __future__ import annotations

import contextlib
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from sheeprl_tpu.telemetry.sinks import write_event  # noqa: E402


def _emit(rec: dict) -> None:
    """One bench record → one schema-validated JSONL line on stdout (the
    driver still parses the LAST stdout line; `event: bench` rides along)."""
    write_event({"event": "bench", **rec}, sys.stdout)


def _progress(msg: str, **fields) -> None:
    """Progress/diagnostic lines → JSONL events on stderr (same schema as
    the in-run telemetry stream)."""
    write_event({"event": "bench_progress", "msg": msg, **fields}, sys.stderr)

# reference README.md:97-148 (v0.5.5, 4 CPU): 65_536-step wall-clock recipes
RECIPE_BASELINE_SECONDS = {"ppo": 81.27, "a2c": 84.76, "sac": 320.21}
RECIPE_EXPS = {"ppo": "ppo_benchmarks", "a2c": "a2c_benchmarks", "sac": "sac_benchmarks"}
RECIPE_TOTAL_STEPS = 65_536

# reference README.md:150-176 (v0.5.5, 4 CPU): 16_384-step micro-benches
DREAMER_BASELINE_SECONDS = {"dv1": 2207.13, "dv2": 906.42, "dv3": 1589.30}
DREAMER_EXPS = {
    "dv1": "dreamer_v1_benchmarks",
    "dv2": "dreamer_v2_benchmarks",
    "dv3": "dreamer_v3_benchmarks",
}
DREAMER_TOTAL_STEPS = int(os.environ.get("BENCH_DREAMER_STEPS", 16_384))


def _timed_cli_run(
    args: list,
    steps: int,
    baseline_seconds: float,
    baseline_steps: int,
    metric: str,
    unit: str = "env steps/sec",
) -> dict:
    """Run a recipe through the CLI (training output → stderr), timing it and
    accounting for a wall-cap stop: SPS is computed over the steps that
    actually ran (utils/run_info.py records a short stop)."""
    from sheeprl_tpu.cli import run
    from sheeprl_tpu.utils import run_info

    run_info.last_run.clear()  # don't inherit a previous leg's policy_step
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(sys.stderr):
        run(args)
    t_end = time.perf_counter()
    elapsed = t_end - t0
    recorded = run_info.last_run.get("policy_step")  # set only on wall-cap stop
    steps_done = steps if recorded is None else int(recorded)
    sps = steps_done / elapsed
    rec = {
        "metric": metric,
        "value": round(sps, 2),
        "unit": unit,
        "vs_baseline": round(sps / (baseline_steps / baseline_seconds), 3),
        "elapsed_seconds": round(elapsed, 2),
        "baseline_seconds": baseline_seconds,
        "steps": steps_done,
    }
    # post-compile window: the loops record the end of their first training
    # burst (run_info.mark_steady) — SPS over everything after it separates
    # sustained throughput from the one-time jit compile + warmup price
    steady_step, steady_t = run_info.last_run.get("steady_step"), run_info.last_run.get("steady_t")
    if steady_t is not None and t_end > steady_t and steps_done > steady_step:
        rec["steady_state_sps"] = round((steps_done - steady_step) / (t_end - steady_t), 2)
        rec["startup_seconds"] = round(steady_t - t0, 2)  # env init + compile + first burst
    if steps_done < steps:
        rec["wall_capped"] = True
    # continuous binding-stage attribution (diag/aggregator.py): the
    # offline trace verdict over the leg's own telemetry streams, stamped
    # onto the record. Informational — bench_compare never gates on it.
    leg_log_dir = run_info.last_run.get("log_dir")
    if leg_log_dir:
        try:
            from sheeprl_tpu.diag.aggregator import binding_stage_for_run

            stage = binding_stage_for_run(leg_log_dir)
            if stage:
                rec["binding_stage"] = stage
        except Exception:
            pass
    try:
        # same basis stamp as bench_dv3.record(): the e2e record labels its
        # own MFU denominator class (vendor peak vs measured host matmul)
        # even when the compute-only leg never ran to copy it from — the
        # label alone, no matmul measurement
        import jax

        from sheeprl_tpu.telemetry.throughput import peak_flops_basis_for

        rec["peak_flops_basis"] = peak_flops_basis_for(jax.devices()[0])
    except Exception:
        pass
    _stamp_memory_peaks(rec)
    return rec


def _stamp_memory_peaks(rec: dict) -> None:
    """Peak host RSS (kernel VmHWM) + device allocator high-water onto a
    bench record — informational, like binding_stage: bench_compare shows
    the drift but never gates on it."""
    try:
        from sheeprl_tpu.telemetry.memory import host_rss_peak_bytes
        from sheeprl_tpu.telemetry.xla import device_memory_stats

        peak = host_rss_peak_bytes()
        if peak:
            rec["peak_rss_bytes"] = int(peak)
        dev = device_memory_stats()
        if dev.get("peak_bytes_in_use"):
            rec["device_peak_bytes"] = int(dev["peak_bytes_in_use"])
    except Exception:
        pass


def bench_recipe(which: str) -> dict:
    """One of the reference's 65_536-step wall-clock recipes end to end:
    ppo / a2c (CartPole) or sac (LunarLanderContinuous)."""
    steps = RECIPE_TOTAL_STEPS
    args = [f"exp={RECIPE_EXPS[which]}", f"algo.total_steps={steps}"]
    wall_cap = os.environ.get("BENCH_RECIPE_WALL_S")
    if wall_cap:
        args.append(f"algo.max_wall_time_s={wall_cap}")
    env_name = "LunarLanderContinuous" if which == "sac" else "CartPole-v1"
    return _timed_cli_run(
        args,
        steps,
        RECIPE_BASELINE_SECONDS[which],
        steps,
        f"{which.upper()} {env_name} {steps}-step policy SPS (reference recipe, end-to-end)",
    )


def bench_dreamer_e2e(which: str) -> dict:
    """The reference's 16_384-step Dreamer micro-bench, end to end through
    the CLI (env stepping + replay + prefetch + train), dummy Atari shapes.

    The run carries its own wall-clock cap (`algo.max_wall_time_s`,
    BENCH_E2E_WALL_S, default 950 s): if the machine is slower than expected
    it stops cleanly at a step boundary and the SPS is computed over the
    steps that actually ran, instead of the subprocess being killed with
    nothing on stdout."""
    steps = DREAMER_TOTAL_STEPS
    wall_cap = float(os.environ.get("BENCH_E2E_WALL_S", 950))
    return _timed_cli_run(
        [
            f"exp={DREAMER_EXPS[which]}",
            "env=dummy",
            "env.id=discrete_dummy",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
            f"algo.total_steps={steps}",
            f"algo.max_wall_time_s={wall_cap}",
            f"buffer.size={steps}",
            "buffer.checkpoint=False",
            "buffer.memmap=False",
            "checkpoint.every=0",
            "checkpoint.save_last=False",
            "metric.log_level=0",
            "algo.player.async_refresh=True",
        ],
        steps,
        DREAMER_BASELINE_SECONDS[which],
        DREAMER_TOTAL_STEPS_REF,
        f"Dreamer{which.upper().replace('DV', 'V')} {steps}-step micro-bench policy "
        "SPS (reference recipe end-to-end: env+replay+train, dummy Atari shapes, ckpt off)",
    )


DREAMER_TOTAL_STEPS_REF = 16_384  # the baseline recipe's step count


def bench_dreamer_fleet(which: str) -> dict:
    """The SAME end-to-end Dreamer recipe as :func:`bench_dreamer_e2e`, run
    through the supervised actor fleet (``algo.fleet.workers``,
    sheeprl_tpu/fleet/) instead of the in-process env loop. Records under
    its own unit — ``env steps/sec (fleet)`` — so `bench_compare.py` gates
    fleet rounds against fleet rounds only; the acceptance bar is that this
    leg keeps env-steps/s at or above the single-process overlap engine's
    on the same recipe (the e2e leg is env-bound: BENCH_r05 measured 10.46
    env-steps/s vs ~1050 grad-steps/s/chip)."""
    steps = DREAMER_TOTAL_STEPS
    wall_cap = float(os.environ.get("BENCH_E2E_WALL_S", 950))
    workers = int(os.environ.get("BENCH_FLEET_WORKERS", 2))
    num_envs = int(os.environ.get("BENCH_FLEET_ENVS", max(4, workers)))
    # BENCH_FLEET_TRANSPORT=socket routes the same recipe over localhost TCP
    # (fleet.transport=socket, sheeprl_tpu/fleet/net.py);
    # BENCH_FLEET_ACT_MODE=inference routes acting through the learner-hosted
    # batched act service (fleet/act_service.py, the Sebulba layout). The
    # unit carries transport, act mode AND worker count, so bench_compare
    # gates like against like only — each topology has its own floor, and a
    # unit with no prior trajectory is auto-skipped (noted, never failed).
    transport = os.environ.get("BENCH_FLEET_TRANSPORT", "mp")
    act_mode = os.environ.get("BENCH_FLEET_ACT_MODE", "worker")
    unit = f"env steps/sec (fleet/{transport}/{act_mode}/w{workers})"
    rec = _timed_cli_run(
        [
            f"exp={DREAMER_EXPS[which]}",
            "env=dummy",
            "env.id=discrete_dummy",
            f"env.num_envs={num_envs}",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
            f"algo.total_steps={steps}",
            f"algo.max_wall_time_s={wall_cap}",
            f"algo.fleet.workers={workers}",
            f"fleet.transport={transport}",
            f"fleet.act_mode={act_mode}",
            f"buffer.size={steps}",
            "buffer.checkpoint=False",
            "buffer.memmap=False",
            "checkpoint.every=0",
            "checkpoint.save_last=False",
            "metric.log_level=0",
        ],
        steps,
        DREAMER_BASELINE_SECONDS[which],
        DREAMER_TOTAL_STEPS_REF,
        f"Dreamer{which.upper().replace('DV', 'V')} {steps}-step micro-bench policy SPS "
        f"(same end-to-end recipe through the {workers}-process actor fleet, "
        f"{transport} transport, act_mode={act_mode})",
        unit=unit,
    )
    rec["fleet_workers"] = workers
    rec["act_mode"] = act_mode
    rec["transport"] = transport
    return rec


def bench_anakin() -> dict:
    """The Anakin leg (sheeprl_tpu/fleet/anakin.py): policy + jax-native env
    fused under vmap inside one jitted scan — the architecture's throughput
    ceiling when the env itself is an array program. `vs_baseline` is the
    ratio over the socket fleet's steady-state 11.81 env-steps/s (BENCH_r06):
    the acceptance bar for this leg is >= 10x."""
    from sheeprl_tpu.config import Config
    from sheeprl_tpu.fleet.anakin import run_anakin

    slots = int(os.environ.get("BENCH_ANAKIN_SLOTS", 1024))
    chunk = int(os.environ.get("BENCH_ANAKIN_CHUNK", 256))
    seconds = float(os.environ.get("BENCH_ANAKIN_SECONDS", 10.0))
    cfg = Config({"seed": 5, "fleet": {"anakin": {"slots": slots, "chunk": chunk}}})
    res = run_anakin(cfg, min_seconds=seconds)
    baseline_sps = 11.81  # BENCH_r06 socket-fleet steady-state env-steps/s
    return {
        "metric": (
            f"Anakin fused act path ({slots} vmapped env slots x {chunk}-step "
            "jitted scan chunks, synthetic jax-native env)"
        ),
        "value": round(res["steps_per_s"], 2),
        "unit": "env steps/sec (fleet/anakin)",
        "vs_baseline": round(res["steps_per_s"] / baseline_sps, 1),
        "elapsed_seconds": round(res["seconds"], 2),
        "steps": res["env_steps"],
        "slots": slots,
        "chunk": chunk,
    }


def _run_subprocess_record(argv: list, budget_s: float) -> dict | None:
    """Run `python bench.py <argv>` as a subprocess with a wall-clock budget;
    return the JSON record from its last stdout line, or None on
    failure/timeout (details to stderr)."""
    cmd = [sys.executable, os.path.abspath(__file__)] + argv
    try:
        proc = subprocess.run(
            cmd, stdout=subprocess.PIPE, stderr=sys.stderr, timeout=budget_s, text=True
        )
    except subprocess.TimeoutExpired:
        _progress(f"{' '.join(argv)} exceeded {budget_s}s budget")
        return None
    if proc.returncode != 0:
        _progress(f"{' '.join(argv)} exited rc={proc.returncode}")
        return None
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    if not lines:
        return None
    try:
        return json.loads(lines[-1])
    except json.JSONDecodeError:
        _progress(f"{' '.join(argv)} last line not JSON: {lines[-1]!r}")
        return None


def _label(rec: dict, platform: str) -> dict:
    """Stamp where a record was measured. A forced-CPU record says so in its
    metric's name too and loses the comparison with the accelerator
    baseline: it is a number about this host, not about the system."""
    import jax

    rec["platform"] = platform
    rec["device_kind"] = str(getattr(jax.devices()[0], "device_kind", ""))
    if platform == "cpu-forced":
        rec["metric"] = f"[cpu-forced via BENCH_FORCE_CPU, not a device measurement] {rec['metric']}"
        rec["vs_baseline"] = None
        for key in ("mfu", "peak_flops_assumed"):
            rec.pop(key, None)
    return rec


def main() -> int:
    arg = sys.argv[1] if len(sys.argv) > 1 else ""
    is_fleet_leg = arg.endswith("_fleet") and arg[: -len("_fleet")] in DREAMER_EXPS
    if arg in RECIPE_EXPS or arg in DREAMER_EXPS or arg in ("dv3_step", "anakin") or is_fleet_leg:
        import bench_dv3

        # first thing every leg does: settle what it measures on (exits
        # non-zero where there is no accelerator and no BENCH_FORCE_CPU)
        platform = bench_dv3.require_accelerator()
        if arg in RECIPE_EXPS:
            rec = bench_recipe(arg)
        elif arg in DREAMER_EXPS:
            rec = bench_dreamer_e2e(arg)
        elif is_fleet_leg:
            rec = bench_dreamer_fleet(arg[: -len("_fleet")])
        elif arg == "anakin":
            with contextlib.redirect_stdout(sys.stderr):
                rec = bench_anakin()
        else:
            with contextlib.redirect_stdout(sys.stderr):
                rec = bench_dv3.record()
        _emit(_label(rec, platform))
        return 0
    if arg:
        print(f"bench: unknown leg {arg!r}", file=sys.stderr)
        return 2

    os.environ.setdefault("SHEEPRL_TPU_PROGRESS", "1024")  # pacing → stderr
    if os.environ.get("BENCH_FORCE_CPU"):
        _progress("CPU run forced via BENCH_FORCE_CPU")
    step_budget = float(os.environ.get("BENCH_STEP_BUDGET_S", 420))
    # pass an ABSOLUTE deadline so the child's timing loop can shrink to
    # what truly remains (its own clock starts after imports/build — a
    # relative budget would overestimate and still get killed)
    os.environ["BENCH_STEP_DEADLINE"] = str(time.time() + step_budget)
    step_rec = _run_subprocess_record(["dv3_step"], step_budget)
    if step_rec is not None:
        _emit(step_rec)
    e2e_budget = float(os.environ.get("BENCH_E2E_BUDGET_S", 1100))
    e2e_rec = _run_subprocess_record(["dv3"], e2e_budget)
    # opt-in fleet e2e leg (BENCH_FLEET=1): the same recipe through the
    # supervised actor fleet, recorded under its own unit so the gate
    # compares fleet rounds against fleet rounds (off by default — it
    # costs another full e2e budget)
    fleet_rec = None
    if os.environ.get("BENCH_FLEET"):
        fleet_budget = float(os.environ.get("BENCH_FLEET_BUDGET_S", 1100))
        fleet_rec = _run_subprocess_record(["dv3_fleet"], fleet_budget)
    if e2e_rec is not None:
        extra = [rec for rec in (step_rec, fleet_rec) if rec is not None]
        if step_rec is not None:
            # surface the utilization figures on the headline record
            for key in ("mfu", "model_flops_per_step", "peak_flops_assumed", "peak_flops_basis"):
                if key in step_rec:
                    e2e_rec[key] = step_rec[key]
        if extra:
            e2e_rec["extra_metrics"] = extra
        _emit(e2e_rec)
        return 0
    if step_rec is not None:
        step_rec["e2e_error"] = (
            "end-to-end leg failed or exceeded its budget; compute-only record promoted"
        )
        if fleet_rec is not None:
            # the fleet leg still ran its full budget: keep it gateable
            step_rec["extra_metrics"] = [fleet_rec]
        _emit(step_rec)
        return 0
    failure = {
        "metric": "DreamerV3 bench",
        "value": 0.0,
        "unit": "env steps/sec",
        "vs_baseline": 0.0,
        "error": "every bench leg failed (no accelerator, or see stderr); nothing was measured",
    }
    if fleet_rec is not None:
        failure["extra_metrics"] = [fleet_rec]
    _emit(failure)
    return 1


if __name__ == "__main__":
    sys.exit(main())
