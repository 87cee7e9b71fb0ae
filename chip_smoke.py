"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a user
calls (`sheeprl_tpu.cli.run` / `sheeprl_tpu.cli.evaluation`, the functions
behind `python -m sheeprl_tpu run|eval`), and checks what comes out:

  python chip_smoke.py              one chip (this is how the driver runs it)
  python chip_smoke.py --chips 4    the data-parallel path on four chips and
                                    the one-device run it is compared with;
                                    no other phase
  python chip_smoke.py --rehearse-cpu [--chips 4]
                                    the same control flow on the CPU at tiny
                                    widths (Pallas in interpret mode, virtual
                                    devices): finds wrong paths and arguments
                                    before chip time is spent. Never a chip run.

One-chip phases:

  dv3      DreamerV3-S at its published widths (exp=dreamer_v3_100k_ms_pacman
           on the dummy env's 64x64x3 uint8 frames; ale-py is not installed).
           Only the step counts are cut: the run passes learning_starts,
           compiles, takes >= 8 gradient steps and writes a checkpoint.
  eval     `cli.evaluation` from that checkpoint.
  ppo      exp=ppo_benchmarks (CartPole vector env) for four updates.
  kernels  what no CPU test can run: the Pallas GRU compiled (not
           interpreted) against the XLA scan, forward and gradients, at the
           XS and S widths; the device replay ring against the host buffer;
           one DV3-S burst with decoupled_rssm + pallas_gru against the same
           burst on the scan path.

Every phase prints one JSON object on its own line (wall and compile seconds,
persistent-cache hits and misses, where the learner's and the player's
programs ran, what the `auto` options resolved to, peak HBM). The LAST line is
`{"ok": true, "device": {"platform", "kind", "count"}}` with the device as JAX
reports it, and the exit code is 0, only if every phase passed. Without an
accelerator (and without --rehearse-cpu) the script exits 2 at once.
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, NamedTuple, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

# -- the recipes ------------------------------------------------------------
# DreamerV3-S as configs/algo/dreamer_v3_S.yaml gives it: dense 512 x 2, GRU
# 512, CNN multiplier 32, 32x32 latents, [T 64, B 16] batches, horizon 15,
# buffer.size 100_000 (so buffer.device_cache=auto resolves as for a user).
DV3_RECIPE = [
    "exp=dreamer_v3_100k_ms_pacman",
    "env=dummy",
    "env.id=discrete_dummy",
    "algo.cnn_keys.encoder=[rgb]",
    "algo.mlp_keys.encoder=[]",
    "fabric.precision=32-true",
]
# the cut: 128 random steps fill two sequence lengths of replay, then one
# gradient step per env step (replay_ratio 1, one env)
DV3_STEPS = ["algo.learning_starts=128", "algo.total_steps=152", "metric.log_every=8"]
DV3_MIN_GRAD_STEPS = 8
# rehearsal only: the same program at widths a CPU compiles in seconds
TINY_WIDTHS = [
    "algo=dreamer_v3_XS",
    "algo.per_rank_batch_size=2",
    "algo.per_rank_sequence_length=8",
    "algo.horizon=3",
    "algo.dense_units=16",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=8",
    "algo.world_model.recurrent_model.dense_units=16",
    "algo.world_model.transition_model.hidden_size=16",
    "algo.world_model.representation_model.hidden_size=16",
    "algo.world_model.discrete_size=4",
    "algo.world_model.stochastic_size=4",
    "buffer.size=512",
]
TINY_STEPS = ["algo.learning_starts=24", "algo.total_steps=36", "metric.log_every=4"]
PPO_RECIPE = ["exp=ppo_benchmarks", "algo.total_steps=2048"]  # 4 updates of 4 envs x 128 steps
PPO_TINY = ["exp=ppo_benchmarks", "algo.total_steps=512", "algo.rollout_steps=32", "algo.update_epochs=2"]

# -- tolerances -------------------------------------------------------------
# The Pallas GRU runs as a training run runs it: float32 at the default matmul
# precision, which on a TPU multiplies in one bf16 pass (3 decimal digits) in
# the kernel and in XLA's scan alike. Two such results differ by their
# rounding, so neither is the yardstick: the exact answer is
# `reference_sequence` under precision "highest", and the kernel may be
# PRECISION_FACTOR times as far from it as the XLA scan at default precision
# is, or within the float32 floor below, whichever is larger. (The kernel is
# not run under "highest" itself: the S backward then asks for 32.8 MB of
# scoped VMEM against the compiler's 16 MB limit.)
PRECISION_FACTOR = 4.0
# float32 floors: hidden states are tanh-bounded (|h| <= 1) and 64 recurrent
# steps of a K=1024 f32 dot leave ~1e-5; each gradient is compared relative
# to its own largest entry
GRU_FWD_ATOL = 1e-3
GRU_GRAD_RTOL = 5e-3
# DV3 burst, GRU as the Pallas kernel vs as the XLA scan: same params, batch
# and keys. Yardstick as above: the scan-path burst under "highest". Gated on
# the world model's losses (where the GRU lives); means over 1024 positions.
BURST_RTOL = 2e-3
BURST_GATED = ("Loss/world_model_loss", "Loss/observation_loss", "Loss/reward_loss",
               "Loss/state_loss", "Loss/continue_loss", "State/kl")
# four chips vs one device on the loop's own first staged batch, default
# precision on both sides. Gated on the world model's losses and state
# statistics: they are computed from the same params and batch before any
# update, so only the reduction order over the split batch and the split
# weights differs. The actor's and critic's losses come after the world
# model's update inside the same step and are Monte Carlo estimates over
# imagined trajectories SAMPLED from categorical distributions: a last-digit
# difference in the updated weights flips samples, so across layouts they
# agree statistically, not digit for digit (on the chip: 0.6% apart under
# dp4, 14% under dp2 x fsdp2, with the world-model losses in agreement);
# they are reported and must be finite.
MESH_GATED = BURST_GATED + ("State/post_entropy", "State/prior_entropy")
MESH_RTOL = 5e-3
MESH_ATOL = 1e-4


# backend compiles (or persistent-cache loads) by jitted function name: a
# program that compiles twice at one shape was traced twice, and on the chip
# a second DV3 compile is most of a minute
_COMPILES: Dict[str, int] = {}


def _count_compile(event: str, _secs: float, fun_name: str = "", **_kw: Any) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES[fun_name] = _COMPILES.get(fun_name, 0) + 1


def _compiled_since(before: Dict[str, int], name: str) -> int:
    return _COMPILES.get(name, 0) - before.get(name, 0)


def _emit(rec: Dict[str, Any], sort_keys: bool = True) -> None:
    sys.__stdout__.write(json.dumps(rec, sort_keys=sort_keys) + "\n")
    sys.__stdout__.flush()


# -- observing a run without changing it -------------------------------------
class Recorded:
    """A jitted callable that also notes where its outputs live. The loops
    are driven through `cli.run`; this only watches what they return."""

    def __init__(self, fn: Callable, keep: Optional[Callable] = None, first: Optional[Callable] = None):
        self.fn, self.keep, self.first = fn, keep, first
        self.calls = 0
        self.devices: set = set()
        self.kept: List[Any] = []

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        import jax

        if self.calls == 0 and self.first is not None:
            self.first(args)
        out = self.fn(*args, **kwargs)
        leaves = jax.tree.leaves(out)
        if any(isinstance(leaf, jax.core.Tracer) for leaf in leaves):
            return out  # the program tracing its own callable (the player's read set): nothing ran
        self.calls += 1
        for leaf in leaves:
            if isinstance(leaf, jax.Array):
                self.devices |= leaf.devices()
        if self.keep is not None:
            self.kept.append(self.keep(out))
        return out

    def __getattr__(self, name: str) -> Any:  # .lower(), ._cache_size()
        return getattr(self.fn, name)


class Made(NamedTuple):
    """One call of a watched factory: what it returned (wrapped) and with
    what arguments it was called."""

    out: Any
    args: tuple
    kwargs: Dict[str, Any]


@contextlib.contextmanager
def recording(module: Any, factory: str, wrap: Callable[[Any], Any]):
    """While active, what `module.factory(...)` returns goes through `wrap`;
    yields the list of `Made` records, one per factory call."""
    orig = getattr(module, factory)
    made: List[Made] = []

    def patched(*args: Any, **kwargs: Any) -> Any:
        made.append(Made(wrap(orig(*args, **kwargs)), args, kwargs))
        return made[-1].out

    setattr(module, factory, patched)
    try:
        yield made
    finally:
        setattr(module, factory, orig)


def _platforms(devices: set) -> List[str]:
    return sorted({f"{d.platform}:{d.id}" for d in devices})


def _finite_metrics(kept: List[Dict[str, Any]]) -> Dict[str, float]:
    """Last value of every recorded metric; raises if any value of any call
    is not finite."""
    import numpy as np

    last: Dict[str, float] = {}
    for i, metrics in enumerate(kept):
        for k, v in metrics.items():
            arr = np.asarray(v, np.float64)
            if not np.isfinite(arr).all():
                raise AssertionError(f"{k} is not finite at train call {i}: {arr}")
            last[k] = float(arr.reshape(-1)[-1])
    return last


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def _run_dir(algo: str) -> str:
    dirs = sorted(glob.glob(os.path.join("logs", "runs", algo, "*", "*", "version_*")), key=os.path.getmtime)
    _require(bool(dirs), f"no run directory under logs/runs/{algo}")
    return dirs[-1]


def _telemetry(run_dir: str) -> List[Dict[str, Any]]:
    with open(os.path.join(run_dir, "telemetry.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


# -- phases -------------------------------------------------------------------
class Smoke:
    def __init__(self, rehearse: bool):
        import jax

        self.rehearse = rehearse
        self.device = jax.devices()[0]
        self.platform = self.device.platform
        self.failed: List[str] = []
        self.ckpt: Optional[str] = None

    # .. plumbing ..............................................................
    def phase(self, name: str, fn: Callable[[], Dict[str, Any]]) -> bool:
        from sheeprl_tpu.telemetry import xla

        c0 = xla.compile_counters()
        t0 = time.perf_counter()
        rec: Dict[str, Any] = {"phase": name}
        try:
            with contextlib.redirect_stdout(sys.stderr):
                rec.update(fn() or {})
            rec["ok"] = True
        except (Exception, SystemExit) as err:  # a failed phase fails the script, after the others ran
            traceback.print_exc(file=sys.stderr)
            rec.update(ok=False, error=f"{type(err).__name__}: {err}"[:2000])
            self.failed.append(name)
        c1 = xla.compile_counters()
        stats = self.device.memory_stats() or {}
        rec.update(
            wall_s=round(time.perf_counter() - t0, 2),
            compile_s=round(c1["compile_seconds"] - c0["compile_seconds"], 2),
            compiles=int(c1["compile_count"] - c0["compile_count"]),
            cache_hits=int(c1["cache_hits"] - c0["cache_hits"]),
            cache_misses=int(c1["cache_misses"] - c0["cache_misses"]),
            peak_hbm_bytes=stats.get("peak_bytes_in_use"),
            rehearsal=self.rehearse,
        )
        _emit(rec)
        return rec["ok"]

    def _dv3_args(self, extra: List[str] = ()) -> List[str]:
        widths = TINY_WIDTHS if self.rehearse else []
        steps = TINY_STEPS if self.rehearse else DV3_STEPS
        return DV3_RECIPE + widths + steps + list(extra)

    def _expect_platform(self, devices: set, what: str) -> None:
        _require(bool(devices), f"{what}: no output was recorded")
        got = {d.platform for d in devices}
        _require(got == {self.platform}, f"{what} ran on {got}, expected {self.platform}")

    # .. the DV3 run through cli.run ..........................................
    def run_dv3(self, extra: List[str] = (), snapshot_first: bool = False) -> Dict[str, Any]:
        """One DV3 run through `cli.run`, watched. Returns the phase record;
        with ``snapshot_first`` also, under ``_first``, what the one-device
        comparison needs: host copies of the first train call's inputs, its
        metrics and placement, and the same train function without the
        mesh's output placement."""
        import jax
        import numpy as np

        from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3
        from sheeprl_tpu.cli import run
        from sheeprl_tpu.config import compose
        from sheeprl_tpu.ops.conv_einsum import resolve_conv_impl

        args = self._dv3_args(extra)
        first_inputs: Dict[str, Any] = {}

        def snapshot(call_args: tuple) -> None:
            first_inputs["args"] = jax.tree.map(_to_host, call_args)
            frames = call_args[3]["rgb"]  # [G, T, B, 64, 64, 3], B over the data axes
            first_inputs["batch"] = {
                "devices": len({s.device for s in frames.addressable_shards}),
                "global_shape": list(frames.shape),
                "shard_shape": list(frames.addressable_shards[0].data.shape),
            }

        def keep(out: Any) -> Any:
            if snapshot_first and "placement" not in first_inputs:
                first_inputs["placement"] = _placement(out[:3])
            return out[3]  # metrics

        threads_before = {t.ident for t in threading.enumerate()}
        compiles_before = dict(_COMPILES)
        with recording(dv3, "make_train_fn", lambda f: Recorded(f, keep, snapshot if snapshot_first else None)) as trains, \
                recording(dv3, "make_player", lambda pair: (pair[0], Recorded(pair[1]))) as players, \
                recording(dv3, "make_sequential_prefetcher", lambda p: p) as prefetchers:
            run(args)
        train, player = trains[0].out, players[0].out[1]

        losses = _finite_metrics(train.kept)
        self._expect_platform(train.devices, "the DV3 train program")
        train_compiles = _compiled_since(compiles_before, "jit(train)")
        _require(train.calls >= 1 and train_compiles == 1,
                 f"the train program compiled {train_compiles} times over {train.calls} calls of one shape")
        _require(player.calls > 0 and player._cache_size() == 1,
                 f"player step compiled {player._cache_size()} variants over {player.calls} calls (retrace)")
        leftover = [t.name for t in threading.enumerate()
                    if t.ident not in threads_before and t.name.startswith("overlap")]
        _require(not leftover, f"overlap engine threads still alive: {leftover}")

        run_dir = _run_dir("dreamer_v3")
        events = _telemetry(run_dir)
        startup = next(e for e in events if e["event"] == "startup")
        shutdown = next(e for e in events if e["event"] == "shutdown")
        overlap = [e for e in events if e["event"] == "overlap"]
        logs = [e for e in events if e["event"] == "log"]
        _require(startup["platform"] == self.platform,
                 f"telemetry heartbeat says platform={startup['platform']}, expected {self.platform}")
        min_steps = 1 if snapshot_first else DV3_MIN_GRAD_STEPS
        _require(shutdown["total_grad_steps"] >= min_steps,
                 f"only {shutdown['total_grad_steps']} gradient steps were taken")
        _require(shutdown["xla"]["retraces"] == 0,
                 f"retraces after warm-up: {shutdown['xla'].get('retrace_attribution')}")
        _require(bool(overlap), "the overlap engine emitted no event (did it start?)")
        for e in logs:
            for k, v in e["metrics"].items():
                _require(math.isfinite(v), f"logged {k}={v} at step {e['step']}")
        ckpts = sorted(glob.glob(os.path.join(run_dir, "checkpoint", "ckpt_*.ckpt")), key=os.path.getmtime)
        _require(bool(ckpts), f"no checkpoint under {run_dir}/checkpoint")
        self.ckpt = os.path.abspath(ckpts[-1])

        cfg = compose("config", args)
        conv = str(cfg.algo.world_model.conv_impl)
        rec = {
            "grad_steps": int(shutdown["total_grad_steps"]),
            "train_calls": train.calls,
            "losses": {k: round(v, 6) for k, v in losses.items() if k.startswith("Loss/")},
            "learner_devices": _platforms(train.devices),
            "player_devices": _platforms(player.devices),
            "telemetry_platform": startup["platform"],
            "device_kind": startup["device_kind"],
            "retraces": int(shutdown["xla"]["retraces"]),
            "train_compiles": train_compiles,
            "device_cache": {"configured": str(cfg.buffer.device_cache), "resolved": type(prefetchers[0].out).__name__},
            "conv_impl": {"configured": conv, "resolved": "einsum" if resolve_conv_impl(conv) else "xla"},
            "pallas_gru": str(cfg.algo.world_model.pallas_gru),
            "overlap": bool(cfg.algo.overlap.enabled),
            "checkpoint_bytes": os.path.getsize(self.ckpt),
            "widths": {
                "dense_units": int(cfg.algo.dense_units),
                "recurrent_state_size": int(cfg.algo.world_model.recurrent_model.recurrent_state_size),
                "cnn_channels_multiplier": int(cfg.algo.world_model.encoder.cnn_channels_multiplier),
                "batch": [int(cfg.algo.per_rank_sequence_length), int(cfg.algo.per_rank_batch_size)],
                "buffer_size": int(cfg.buffer.size),
            },
        }
        if snapshot_first:
            # the same factory call again, minus the mesh's output placement
            unplaced = dv3.make_train_fn(*trains[0].args, **{**trains[0].kwargs, "state_shardings": None})
            rec["_first"] = {
                "train": unplaced,
                "args": first_inputs["args"],
                "placement": first_inputs["placement"],
                "batch": first_inputs["batch"],
                "metrics": {k: float(np.asarray(v).reshape(-1)[0]) for k, v in train.kept[0].items()},
            }
        return rec

    def phase_dv3(self) -> Dict[str, Any]:
        from sheeprl_tpu.native import native_status
        from sheeprl_tpu.telemetry.throughput import peak_bytes_per_s_for, peak_flops_for

        rec = self.run_dv3()
        rec["native_gather"] = native_status()
        if not self.rehearse:
            # an attached accelerator the peak table does not know would give
            # every roofline record of a run no roof
            peaks = {"flops": peak_flops_for(self.device), "bytes_per_s": peak_bytes_per_s_for(self.device)}
            _require(None not in peaks.values(), f"no peak for device_kind {self.device.device_kind!r}")
            rec["peaks"] = peaks
        return rec

    def phase_eval(self) -> Dict[str, Any]:
        from sheeprl_tpu.cli import evaluation

        _require(self.ckpt is not None, "no checkpoint: the dv3 phase did not finish")
        out = _Tee(sys.stderr)
        with contextlib.redirect_stdout(out):
            evaluation([f"checkpoint_path={self.ckpt}"])
        lines = [ln for ln in out.text().splitlines() if ln.startswith("Test - Reward:")]
        _require(bool(lines), "evaluation printed no 'Test - Reward'")
        reward = float(lines[-1].split(":", 1)[1])
        _require(math.isfinite(reward), f"evaluation reward {reward}")
        return {"reward": reward, "checkpoint": os.path.basename(self.ckpt)}

    def phase_ppo(self) -> Dict[str, Any]:
        from sheeprl_tpu.algos.ppo import ppo
        from sheeprl_tpu.cli import run

        compiles_before = dict(_COMPILES)
        with recording(ppo, "make_update_fn", lambda f: Recorded(f, lambda out: out[2])) as updates, \
                recording(ppo, "make_act_fn", Recorded) as acts:
            run(PPO_TINY if self.rehearse else PPO_RECIPE)
        update, act = updates[0].out, acts[0].out
        losses = _finite_metrics(update.kept)
        self._expect_platform(update.devices, "the PPO update program")
        _require(update.calls >= 4, f"only {update.calls} PPO updates ran")
        update_compiles = _compiled_since(compiles_before, "jit(update)")
        _require(update_compiles == 1 and act._cache_size() == 1,
                 f"retrace: update compiled {update_compiles} times, act {act._cache_size()} variants")
        return {
            "updates": update.calls,
            "update_compiles": update_compiles,
            "losses": {k: round(v, 6) for k, v in losses.items()},
            "learner_devices": _platforms(update.devices),
            "player_devices": _platforms(act.devices),
        }

    # .. what has never left interpret mode / the CPU ..........................
    def phase_kernels(self) -> Dict[str, Any]:
        return {
            "pallas_mode": "interpret" if self.rehearse else "compiled",
            "gru": self._gru_parity(),
            "ring": self._ring_parity(),
            "burst": self._burst_parity(),
        }

    def _gru_parity(self) -> Dict[str, Any]:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from sheeprl_tpu.ops.pallas_gru import fits_vmem, gru_sequence, reference_sequence

        interpret = self.rehearse
        sizes = {"tiny": (16, 8, 6, 4)} if self.rehearse else {"XS": (256, 256, 64, 16), "S": (512, 512, 64, 16)}
        out: Dict[str, Any] = {}
        problems: List[str] = []
        for name, (F, H, T, B) in sizes.items():
            _require(fits_vmem(F, H), f"{name} does not pass fits_vmem")
            k = jax.random.split(jax.random.key(0), 5)
            feats = jax.random.normal(k[0], (T, B, F), jnp.float32)
            first = jnp.zeros((T, B, 1), jnp.float32).at[0].set(1.0).at[T // 2, 1].set(1.0)
            h_first = jax.random.normal(k[1], (H,), jnp.float32) * 0.5
            w = jax.random.normal(k[2], (F + H, 3 * H), jnp.float32) / np.sqrt(F + H)
            scale = 1.0 + 0.1 * jax.random.normal(k[3], (3 * H,), jnp.float32)
            bias = 0.1 * jax.random.normal(k[4], (3 * H,), jnp.float32)
            args = (feats, first, h_first, w, scale, bias)

            def kernel(*a):
                return gru_sequence(*a, interpret)

            def loss(fn):
                return lambda f, h0, w_, s, b: jnp.sum(fn(f, first, h0, w_, s, b) ** 2)

            def grads(fn):
                return jax.jit(jax.grad(loss(fn), argnums=(0, 1, 2, 3, 4)))(feats, h_first, w, scale, bias)

            # fresh jits inside the precision context: it is read at trace time
            with jax.default_matmul_precision("highest"):
                exact = np.asarray(jax.jit(reference_sequence)(*args))
                g_exact = grads(reference_sequence)
            scan = np.asarray(jax.jit(reference_sequence)(*args))
            got = np.asarray(jax.jit(kernel)(*args))
            g_scan, g_kernel = grads(reference_sequence), grads(kernel)

            def rel(a, b):
                return float(np.max(np.abs(np.asarray(a) - np.asarray(b))) / max(float(np.max(np.abs(b))), 1e-30))

            fwd = {"kernel": float(np.max(np.abs(got - exact))), "xla_scan": float(np.max(np.abs(scan - exact)))}
            labels = ("feats", "h_first", "w", "scale", "bias")
            grad = {
                label: {"kernel": rel(gk, ge), "xla_scan": rel(gs, ge)}
                for label, gk, gs, ge in zip(labels, g_kernel, g_scan, g_exact)
            }
            out[name] = {
                "F": F, "H": H, "T": T, "B": B, "fwd_max_abs_err": fwd, "grad_max_rel_err": grad,
                "kernel_vs_xla_scan_max_abs": float(np.max(np.abs(got - scan))),
            }
            if not (np.isfinite(got).all() and all(np.isfinite(np.asarray(g)).all() for g in g_kernel)):
                problems.append(f"GRU {name}: the kernel's output or gradients are not finite")
            if fwd["kernel"] > max(PRECISION_FACTOR * fwd["xla_scan"], GRU_FWD_ATOL):
                problems.append(f"GRU {name} forward: {fwd}")
            for label, e in grad.items():
                if e["kernel"] > max(PRECISION_FACTOR * e["xla_scan"], GRU_GRAD_RTOL):
                    problems.append(f"GRU {name} grad[{label}]: {e}")
        _require(not problems, "; ".join(problems) + f" | measured: {json.dumps(out)}")
        return out

    def _ring_parity(self) -> Dict[str, Any]:
        """Scatter into the device ring and gather a batch from it: the rows
        must be those of the host buffer, exactly (the ring copies, it does
        no arithmetic). Includes an incremental sync after the first gather."""
        import numpy as np

        from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer
        from sheeprl_tpu.data.device_ring import DeviceRingPrefetcher

        size, n_envs, T, B = 256, 2, 16, 8
        rb = EnvIndependentReplayBuffer(size, n_envs, obs_keys=("rgb",), buffer_cls=SequentialReplayBuffer)
        rng = np.random.default_rng(0)

        def add(n: int) -> None:
            for _ in range(n):
                rb.add({
                    "rgb": rng.integers(0, 255, (1, n_envs, 64, 64, 3), dtype=np.uint8),
                    "rewards": rng.normal(size=(1, n_envs, 1)).astype(np.float32),
                    "is_first": np.zeros((1, n_envs, 1), np.float32),
                })

        ring = DeviceRingPrefetcher(rb, batch_size=B, sequence_length=T, cnn_keys=("rgb",), device=self.device)
        checked = 0
        for n_new in (96, 5, 300):  # first fill, an incremental sync, a wrap-around
            add(n_new)
            batch = ring.take(1)
            t_idx, env_order = ring._last_idx
            for key in ("rgb", "rewards"):
                got = np.asarray(batch[key])[0]  # [T, B, ...]
                for b in range(B):
                    want = np.asarray(rb.buffer[env_order[b]][key])[t_idx[0, :, b], 0]
                    _require(np.array_equal(got[:, b], want), f"ring {key} column {b} differs from the host buffer")
                    checked += 1
            _require(batch["rgb"].dtype == np.uint8 and batch["rgb"].devices() == {self.device},
                     "ring batch left its device or dtype")
        return {"columns_checked": checked, "device": _platforms({self.device})}

    def _burst_parity(self) -> Dict[str, Any]:
        """One gradient burst of the loop's own train function, decoupled
        RSSM, GRU as the Pallas kernel vs as the XLA scan: same params, batch
        and keys (see BURST_RTOL for the yardstick)."""
        import gymnasium as gym
        import jax
        import jax.numpy as jnp
        import numpy as np

        from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
        from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import build_optimizers, make_train_fn
        from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
        from sheeprl_tpu.config import compose
        from sheeprl_tpu.parallel import build_distributed

        n_act = 4
        mode = "interpret" if self.rehearse else "True"

        def burst(pallas: str, precision: Optional[str] = None) -> Dict[str, float]:
            cfg = compose("config", DV3_RECIPE + (TINY_WIDTHS if self.rehearse else []) + [
                "algo.world_model.decoupled_rssm=True", f"algo.world_model.pallas_gru={pallas}"])
            T, B = int(cfg.algo.per_rank_sequence_length), int(cfg.algo.per_rank_batch_size)
            dist = build_distributed(cfg)
            space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8)})
            wm, actor, critic, params = build_agent(dist, cfg, space, [n_act], False, jax.random.key(0))
            txs, opt_states = build_optimizers(cfg, params)
            train = make_train_fn(wm, actor, critic, txs, cfg, False, [n_act])
            rng = np.random.default_rng(0)
            batch = {
                "rgb": jnp.asarray(rng.integers(0, 255, (1, T, B, 64, 64, 3), np.uint8)),
                "actions": jnp.asarray(np.eye(n_act, dtype=np.float32)[rng.integers(0, n_act, (1, T, B))]),
                "rewards": jnp.asarray(rng.standard_normal((1, T, B, 1)), jnp.float32),
                "terminated": jnp.zeros((1, T, B, 1), jnp.float32),
                "truncated": jnp.zeros((1, T, B, 1), jnp.float32),
                "is_first": jnp.zeros((1, T, B, 1), jnp.float32).at[0, T // 2, 1].set(1.0),
            }
            with jax.default_matmul_precision(precision) if precision else contextlib.nullcontext():
                _, _, _, metrics = train(params, opt_states, init_moments(), batch,
                                         jax.random.split(jax.random.key(7), 1))
            self._expect_platform(set().union(*(v.devices() for v in metrics.values())), "the DV3 burst")
            return {k: float(np.asarray(v)[0]) for k, v in metrics.items()}

        exact, scan, pallas = burst("False", "highest"), burst("False"), burst(mode)
        problems = [f"{k}={v} on the Pallas path" for k, v in pallas.items() if not math.isfinite(v)]
        gated = {}
        for k in BURST_GATED:
            scale = max(abs(exact[k]), 1e-6)
            e = {"pallas": abs(pallas[k] - exact[k]) / scale, "xla_scan": abs(scan[k] - exact[k]) / scale}
            gated[k] = e
            if e["pallas"] > max(PRECISION_FACTOR * e["xla_scan"], BURST_RTOL):
                problems.append(f"burst {k}: pallas {pallas[k]}, scan {scan[k]}, exact {exact[k]}")
        rec = {"rel_err_vs_exact": gated, "world_model_loss": {
            "pallas": pallas["Loss/world_model_loss"], "xla_scan": scan["Loss/world_model_loss"],
            "exact": exact["Loss/world_model_loss"]}}
        _require(not problems, "; ".join(problems) + f" | measured: {json.dumps(rec)}")
        return rec

    # .. four chips ............................................................
    def phase_mesh(self, label: str, mesh_args: List[str]) -> Dict[str, Any]:
        """The DV3 loop through `cli.run` on a four-device mesh, then the
        loop's own first staged batch again through the loop's train function
        (the same `make_train_fn` call) on ONE device of the host. The loop's sample stream depends on the
        mesh (it takes a gradient step every world_size env steps and sizes
        the global batch by the data-parallel size), so whole runs are not
        comparable step by step; one staged batch through one function is."""
        import jax
        import numpy as np

        rec = self.run_dv3(extra=mesh_args, snapshot_first=True)
        first = rec.pop("_first")
        placement = first["placement"]
        _require(placement["devices"] == 4, f"{label}: state lives on {placement['devices']} devices, not 4")
        _require(not placement["whole_on_one_device"],
                 f"{label}: leaves sit whole on one device: {placement['whole_on_one_device'][:5]}")
        _require(len({d for d in rec["learner_devices"]}) == 4, f"{label}: outputs on {rec['learner_devices']}")
        batch = first["batch"]
        _require(batch["devices"] == 4 and batch["shard_shape"][2] * 4 == batch["global_shape"][2],
                 f"{label}: the staged batch is not split four ways: {batch}")

        one = jax.devices()[0]
        args = jax.tree.map(lambda leaf: _from_host(leaf, one), first["args"], is_leaf=_is_host_key)
        _, _, _, metrics = first["train"](*args)
        compared, problems = {}, []
        for k, v in metrics.items():
            got, want = first["metrics"][k], float(np.asarray(v).reshape(-1)[0])
            _require(v.devices() == {one}, f"one-device replay of {k} ran on {v.devices()}")
            err = abs(got - want)
            compared[k] = {"mesh": got, "one_device": want, "rel_diff": err / max(abs(want), MESH_ATOL)}
            if not math.isfinite(got):
                problems.append(f"{k}={got} on the mesh")
            elif k in MESH_GATED and err > MESH_ATOL + MESH_RTOL * abs(want):
                problems.append(f"{k}: mesh {got} vs one device {want}")
        _require(not problems, f"{label}: " + "; ".join(problems) + f" | measured: {json.dumps(compared)}")
        rec.update(
            mesh=label,
            compared="the loop's first staged batch through the loop's train function, mesh vs one device",
            max_rel_diff=max(compared[k]["rel_diff"] for k in MESH_GATED),
            losses_mesh_vs_one_device=compared,
            placement={k: v for k, v in placement.items() if k != "whole_on_one_device"},
            batch=batch,
        )
        return rec


class _Tee:
    def __init__(self, stream: Any):
        self._stream, self._parts = stream, []

    def write(self, s: str) -> int:
        self._parts.append(s)
        return self._stream.write(s)

    def flush(self) -> None:
        self._stream.flush()

    def isatty(self) -> bool:
        return False

    def text(self) -> str:
        return "".join(self._parts)


def _is_host_key(x: Any) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], str) and x[0] == "__prng_key__"


def _to_host(leaf: Any) -> Any:
    """Host copy of one (possibly sharded) train-call argument; PRNG key
    arrays travel as their raw data."""
    import jax
    import numpy as np

    if isinstance(leaf, jax.Array) and jax.dtypes.issubdtype(leaf.dtype, jax.dtypes.prng_key):
        return ("__prng_key__", np.asarray(jax.random.key_data(leaf)))
    return np.asarray(leaf)


def _from_host(leaf: Any, device: Any) -> Any:
    import jax

    if _is_host_key(leaf):
        return jax.device_put(jax.random.wrap_key_data(leaf[1]), device)
    return jax.device_put(leaf, device)


def _placement(state: Any) -> Dict[str, Any]:
    """Where the train step left params / optimizer state / moments: how many
    distinct devices hold shards, how many leaves are sharded vs replicated,
    and which leaves sit whole on a single device (there should be none)."""
    import jax

    devices: set = set()
    sharded = replicated = 0
    whole: List[str] = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        if not isinstance(leaf, jax.Array):
            continue
        held = {s.device for s in leaf.addressable_shards}
        devices |= held
        if len(held) == 1:
            whole.append(jax.tree_util.keystr(path))
        elif leaf.sharding.is_fully_replicated:
            replicated += 1
        else:
            _require(all(s.data.size < leaf.size for s in leaf.addressable_shards),
                     f"{jax.tree_util.keystr(path)} is 'sharded' but a device holds all of it")
            sharded += 1
    return {"devices": len(devices), "sharded_leaves": sharded, "replicated_leaves": replicated,
            "whole_on_one_device": whole}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the data-parallel path on four chips and its one-device comparison, nothing else")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny widths on the CPU backend (virtual devices, Pallas interpreted); not a chip run")
    opts = ap.parse_args()

    if opts.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        from sheeprl_tpu.utils.virtual_mesh import force_virtual_cpu_mesh

        force_virtual_cpu_mesh(opts.chips)
    import jax

    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    devices = jax.devices()
    if devices[0].platform == "cpu" and not opts.rehearse_cpu:
        print("chip_smoke: JAX found no accelerator (platform cpu). This script proves the chip path; "
              "use --rehearse-cpu to rehearse its control flow.", file=sys.stderr)
        return 2
    if len(devices) < opts.chips:
        print(f"chip_smoke: --chips {opts.chips} but JAX sees {len(devices)} device(s)", file=sys.stderr)
        return 2

    from sheeprl_tpu.utils.utils import enable_compilation_cache

    enable_compilation_cache()  # one cache for every phase, not only those behind cli.run
    _emit({
        "phase": "setup",
        "jax": jax.__version__,
        "devices": [f"{d.platform}:{d.id}" for d in devices],
        "device_kind": devices[0].device_kind,
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "rehearsal": opts.rehearse_cpu,
    })
    smoke = Smoke(opts.rehearse_cpu)
    checkout = os.getcwd()
    scratch = tempfile.mkdtemp(prefix="chip_smoke_")  # logs/, the 1.2 GB memmap, checkpoints
    os.chdir(scratch)
    try:
        if opts.chips == 4:
            smoke.phase("dv3_dp4", lambda: smoke.phase_mesh("dp4", ["fabric.devices=4"]))
            smoke.phase("dv3_dp2_fsdp2", lambda: smoke.phase_mesh(
                "dp2xfsdp2", ["fabric.devices=4", "fabric.mesh.dp=2", "fabric.mesh.fsdp=2"]))
        else:
            if smoke.phase("dv3", smoke.phase_dv3):
                smoke.phase("eval", smoke.phase_eval)
            else:
                smoke.failed.append("eval")  # needs the checkpoint
            smoke.phase("ppo", smoke.phase_ppo)
            smoke.phase("kernels", smoke.phase_kernels)
    finally:
        os.chdir(checkout)
        shutil.rmtree(scratch, ignore_errors=True)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if smoke.failed:
        _emit({"ok": False, "failed": smoke.failed, "device": device}, sort_keys=False)
        return 1
    final: Dict[str, Any] = {"ok": True, "device": device}
    if opts.rehearse_cpu:
        final["rehearsal"] = True  # the device above is the CPU: never reported as a chip run
    _emit(final, sort_keys=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
