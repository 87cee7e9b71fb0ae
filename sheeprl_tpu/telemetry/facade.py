"""The `Telemetry` facade — one object per train loop that owns metric
aggregation, span timing, XLA health counters, throughput/MFU accounting and
every sink (TensorBoard, JSONL event stream, console heartbeat).

Loops use five calls:

    telem = Telemetry.setup(cfg, log_dir, rank, logger=logger,
                            aggregator_keys=AGGREGATOR_KEYS)
    telem.tick(policy_step)                  # top of each iteration:
                                             # StepTraceAnnotation + windowed
                                             # profiler capture
    with telem.span("Time/train_time"): ...  # host span + device TraceAnnotation
    telem.record_grad_steps(n)               # throughput accounting
    telem.log(policy_step)                   # flush one log interval
    telem.close()                            # end-of-run summary event

`telem.aggregator` is a real `MetricAggregator`, so existing
``aggregator.update(...)`` call sites keep working unchanged, and the legacy
`utils.timer` shim drains into the same span tracker this facade reads.
"""
from __future__ import annotations

import os
import sys
import time
from typing import Any, Dict, Optional

from ..utils.metric import MetricAggregator
from . import xla as _xla
from .memory import MemorySampler, host_rss_bytes, memory_snapshot
from .sinks import DEFAULT_JSONL_MAX_BYTES, ConsoleHeartbeat, JsonlSink
from .spans import GLOBAL_TRACKER, Span, SpanTracker
from .schema import SCHEMA_VERSION
from .throughput import (
    ThroughputTracker,
    cost_of_lowered,
    peak_bytes_per_s_record,
    peak_flops_record,
    roofline_record,
)


def _device_info() -> Dict[str, Any]:
    try:
        import jax

        dev = jax.devices()[0]
        return {
            "platform": str(dev.platform),
            "device_kind": str(getattr(dev, "device_kind", "")),
            "devices": int(jax.device_count()),
        }
    except Exception:
        return {"platform": "unknown", "device_kind": "", "devices": 0}


class Telemetry:
    """Unified observability facade for one training loop."""

    def __init__(
        self,
        cfg: Any = None,
        log_dir: Optional[str] = None,
        rank: int = 0,
        logger: Any = None,
        aggregator_keys: Any = None,
        tracker: Optional[SpanTracker] = None,
    ) -> None:
        sel = (lambda p, d=None: cfg.select(p, d)) if cfg is not None else (lambda p, d=None: d)
        self.rank = int(rank)
        self.log_dir = log_dir
        self.logger = logger
        log_level = sel("metric.log_level", 1)
        self.enabled = bool(sel("metric.telemetry.enabled", True)) and (log_level or 0) > 0
        # `metric.disable_timer` (benchmark configs) strips span timing
        # overhead from the hot loop, exactly as it did for the legacy timer
        self._span_enabled = not bool(sel("metric.disable_timer", False))
        self.tracker = tracker if tracker is not None else GLOBAL_TRACKER
        # a previous in-process run (p2e exploration → finetuning, tests) may
        # have left undrained spans in the shared tracker; start clean
        self.tracker.compute(reset=True)
        self.throughput = ThroughputTracker(world_size=int(sel("fabric.devices", 1) or 1))
        self.detector = _xla.RETRACE_DETECTOR

        metrics_cfg = sel("metric.aggregator.metrics") or {}
        if aggregator_keys is not None:
            metrics_cfg = {k: v for k, v in metrics_cfg.items() if k in aggregator_keys}
        self.aggregator = MetricAggregator(metrics_cfg)

        self._info = _device_info()
        self._info.update(
            rank=self.rank,
            world_size=int(sel("fabric.devices", 1) or 1),
            algo=str(sel("algo.name", "") or ""),
            run_name=str(sel("run_name", "") or ""),
            # host RSS on the heartbeat: on CPU-only backends this is the
            # only memory figure the run has, and its absence used to read
            # as "memory telemetry not wired" rather than "no accelerator"
            rss_bytes=host_rss_bytes(),
        )

        # sinks — JSONL only on rank 0 (one stream per run, not per host);
        # size-bounded: past jsonl_max_bytes the file rolls to .1/.2/… so a
        # week-long run cannot fill the disk (diag readers follow segments)
        self.jsonl: Optional[JsonlSink] = None
        if self.enabled and self.rank == 0 and log_dir and bool(sel("metric.telemetry.jsonl", True)):
            max_bytes = sel("metric.telemetry.jsonl_max_bytes")
            self.jsonl = JsonlSink(
                os.path.join(log_dir, "telemetry.jsonl"),
                max_bytes=DEFAULT_JSONL_MAX_BYTES if max_bytes is None else int(max_bytes),
                # rotation happens inside the sink (not through _emit), so
                # mirror the marker into the scrape registry via callback
                on_rotate=lambda marker: self.prom.observe_event(marker)
                if self.prom is not None
                else None,
            )
            # post-run callers (the bench drivers stamping binding_stage
            # onto their records) need the stream's location
            from ..utils import run_info

            run_info.last_run["log_dir"] = str(log_dir)
        # the diag config governs the live plane (aggregator window, SLO
        # rules, per-metric bucket overrides): the run's own `diag` section
        # when composed, else the packaged configs/diag/default.yaml
        self._diag_cfg = None
        if self.enabled and self.rank == 0:
            try:
                from ..diag.doctor import _load_diag_cfg

                self._diag_cfg = _load_diag_cfg(cfg)
            except Exception:
                self._diag_cfg = None

        def dsel(path: str, default: Any = None) -> Any:
            c = self._diag_cfg
            if c is None or not hasattr(c, "select"):
                return default
            val = c.select(path, default)
            return default if val is None else val

        # the central live aggregator (diag/aggregator.py): windowed rollups
        # + binding-stage attribution + SLO burn alerts over this process's
        # own events plus everything the relay forwards. Rank 0 only — the
        # controlling host is where all relayed streams converge.
        self.live = None
        if self.enabled and self.rank == 0 and bool(dsel("diag.live.enabled", True)):
            try:
                from ..diag.aggregator import LiveAggregator

                self.live = LiveAggregator(self._diag_cfg, emit=None, registry=None)
            except Exception as err:
                print(f"[telemetry] live aggregator disabled: {err}", file=sys.stderr)
                self.live = None
        # live Prometheus export (diag/prometheus.py): a /metrics endpoint
        # fed by mirroring the same events the JSONL sink gets. Off by
        # default (port 0); rank 0 only — one scrape surface per run. The
        # same server answers GET /live with the aggregator snapshot.
        self.prom = None
        self._prom_server = None
        self._live_path: Optional[str] = None
        prom_port = int(sel("metric.telemetry.prometheus_port", 0) or 0)
        if self.enabled and self.rank == 0 and prom_port > 0:
            try:
                from ..diag.prometheus import Registry, start_http_server

                self.prom = Registry()
                buckets = dsel("diag.prometheus.buckets")
                if buckets:
                    bd = buckets.to_dict() if hasattr(buckets, "to_dict") else buckets
                    if isinstance(bd, dict):
                        self.prom.set_bucket_overrides(bd)
                prom_host = str(sel("metric.telemetry.prometheus_host", "127.0.0.1"))
                self._prom_server = start_http_server(
                    self.prom, prom_port, host=prom_host, aggregator=self.live
                )
                if log_dir:
                    # discovery file for `sheeprl_tpu top`: where /live is
                    self._live_path = os.path.join(log_dir, "live.json")
                    self._write_live_discovery(prom_host, prom_port)
            except Exception as err:
                print(f"[telemetry] prometheus export disabled: {err}", file=sys.stderr)
                self.prom = None
                self._prom_server = None
        if self.live is not None:
            # wired AFTER the registry exists: alerts land on the main
            # stream via _emit and relayed events federate into /metrics
            self.live.emit = self._emit
            self.live.registry = self.prom
        # the startup heartbeat is intentionally independent of log_level:
        # a run degraded to cpu-fallback must say so even with metrics off
        hb_on = bool(sel("metric.telemetry.heartbeat", True))
        self.heartbeat = ConsoleHeartbeat(rank=self.rank, enabled=hb_on)

        # XLA health baselines: report per-run deltas of process-wide counters
        self._xla0 = _xla.compile_counters()
        self._xla_last = dict(self._xla0)
        self._breakdown0 = _xla.compile_breakdown()
        self._retrace0 = self.detector.retrace_count()
        self._attr_seen = len(self.detector.attribution())

        # roofline registrations (per jitted fn) and the lazily-measured
        # device peaks they classify against (the CPU bandwidth measurement
        # costs ~0.1 s — paid once, on the first registration)
        self._rooflines: Dict[str, Dict[str, Any]] = {}
        self._roofline_peaks: Optional[Dict[str, Any]] = None

        # cadenced memory sampling on the learner's own stream: host RSS
        # always (the CPU container still grows a watermark series), HBM
        # stats where the backend reports them
        self._mem_sampler: Optional[MemorySampler] = None
        self._last_step = 0
        if self.enabled and self.rank == 0 and bool(dsel("diag.mem.enabled", True)):
            self._mem_sampler = MemorySampler(
                self._emit,
                role="learner",
                interval_s=float(dsel("diag.mem.interval_s", 5.0) or 5.0),
                census_every=int(dsel("diag.mem.census_every", 6) or 0),
                step_fn=lambda: self._last_step,
            ).start()

        # step annotation + windowed profiler capture
        self._annotate_steps = self.enabled and bool(sel("metric.telemetry.step_annotation", True))
        self._step_ann: Any = None
        self.trace_every = int(sel("metric.telemetry.trace_every", 0) or 0) if self.enabled else 0
        self.trace_window = int(sel("metric.telemetry.trace_window", 256) or 256)
        self.trace_dir = str(
            sel("metric.telemetry.trace_dir")
            or (os.path.join(log_dir, "xprof") if log_dir else "logs/xprof")
        )
        self._tracing = False
        self._trace_start_step = 0
        self._last_trace_step = 0
        self._closed = False

        self.heartbeat.startup(self._info)
        self._emit({"event": "startup", "schema_version": SCHEMA_VERSION, **self._info})

    # -- construction ------------------------------------------------------
    @classmethod
    def setup(
        cls,
        cfg: Any,
        log_dir: Optional[str],
        rank: int = 0,
        logger: Any = None,
        aggregator_keys: Any = None,
    ) -> "Telemetry":
        return cls(cfg, log_dir, rank, logger=logger, aggregator_keys=aggregator_keys)

    def _write_live_discovery(self, host: str, port: int) -> None:
        """Drop ``<log_dir>/live.json`` so `sheeprl_tpu top` can find the
        running aggregator's /live endpoint from just the run dir."""
        if self._live_path is None:
            return
        try:
            import json

            actual = int(getattr(self._prom_server, "port", port) or port)
            with open(self._live_path, "w") as fh:
                json.dump(
                    {
                        "url": f"http://{host}:{actual}/live",
                        "metrics_url": f"http://{host}:{actual}/metrics",
                        "pid": os.getpid(),
                        "t": time.time(),
                    },
                    fh,
                )
        except Exception:
            self._live_path = None

    # -- sinks -------------------------------------------------------------
    def _emit(self, rec: Dict[str, Any]) -> None:
        if self.jsonl is not None:
            self.jsonl.write(rec)
        if self.prom is not None:
            # mirror into the live scrape surface. Writes follow the same
            # rule as MetricAggregator — the learner thread owns the hot
            # paths (log/overlap); background emitters (ckpt writer,
            # watchdog) only touch their own counters/histograms, each
            # guarded by its per-metric lock.
            try:
                self.prom.observe_event(rec)
            except Exception:
                pass
        if self.live is not None:
            # the aggregator sees the learner's own stream too — rollups and
            # binding-stage attribution need both sides of every trace
            try:
                self.live.ingest(rec)
            except Exception:
                pass

    def emit(self, rec: Dict[str, Any]) -> None:
        """Write one schema-validated event to the JSONL stream — the public
        hook subsystems (resilience, serving) use; safe from any thread
        (JsonlSink locks) and a no-op when the sink is off/closed."""
        self._emit(rec)

    def ingest_relayed(self, batch: Dict[str, Any]) -> None:
        """Hand one relayed telemetry batch (fleet T_TELEM frame, gateway
        ``POST /admin/telemetry`` body) to the live aggregator. Relayed
        events are validated there and NEVER written to this process's
        JSONL — the emitting process's local file is the durable copy, and
        doctor's stream merge must not see any event twice."""
        if self.live is not None:
            try:
                self.live.ingest_batch(batch)
            except Exception:
                pass

    # -- spans / annotations ----------------------------------------------
    def span(self, name: str, **counts: float) -> Span:
        return Span(name, tracker=self.tracker, enabled=self._span_enabled, annotate=self.enabled, **counts)

    def tick(self, policy_step: int) -> None:
        """Call at the top of each loop iteration: rotates the
        `jax.profiler.StepTraceAnnotation` so XProf groups device activity by
        policy step, and opens/closes the windowed on-demand trace capture."""
        if self._step_ann is not None:
            self._exit_step_ann()
        if self._annotate_steps:
            try:
                import jax.profiler as prof

                self._step_ann = prof.StepTraceAnnotation("train", step_num=int(policy_step))
                self._step_ann.__enter__()
            except Exception:
                self._step_ann = None
        if self.trace_every > 0:
            self._windowed_trace(int(policy_step))

    def _exit_step_ann(self) -> None:
        try:
            self._step_ann.__exit__(None, None, None)
        except Exception:
            pass
        self._step_ann = None

    def _windowed_trace(self, policy_step: int) -> None:
        try:
            import jax.profiler as prof

            if not self._tracing and policy_step - self._last_trace_step >= self.trace_every:
                prof.start_trace(self.trace_dir)
                self._tracing = True
                self._trace_start_step = policy_step
                self._emit(
                    {"event": "trace", "step": policy_step, "action": "started", "trace_dir": self.trace_dir}
                )
            elif self._tracing and policy_step - self._trace_start_step >= self.trace_window:
                prof.stop_trace()
                self._tracing = False
                # gap measured from the STOP: trace_window >= trace_every must
                # still pause trace_every steps between captures, not restart
                # immediately (continuous profiling)
                self._last_trace_step = policy_step
                self._emit(
                    {"event": "trace", "step": policy_step, "action": "stopped", "trace_dir": self.trace_dir}
                )
        except Exception:
            # an already-active outer trace (cli profiler) or an unsupported
            # backend must never kill training
            self._tracing = False

    # -- metric / throughput recording ------------------------------------
    def update(self, name: str, value: Any) -> None:
        self.aggregator.update(name, value)

    def record_grad_steps(self, n: int) -> None:
        self.throughput.record_grad_steps(n)

    def set_model_flops(self, flops: Optional[float]) -> None:
        """Register per-grad-step model FLOPs (e.g. from
        `throughput.flops_of_lowered`); enables in-run MFU in log records."""
        if flops is None:
            return
        import jax

        rec = peak_flops_record(jax.devices()[0])
        self.throughput.set_model_flops(flops, rec.get("peak_flops"), jax.device_count())

    def instrument(self, fn: Any, name: Optional[str] = None) -> Any:
        """Wrap a python callable before `jax.jit` so retraces are counted
        and attributed (see `telemetry.xla.RetraceDetector`)."""
        return self.detector.wrap(fn, name)

    # -- roofline ----------------------------------------------------------
    def _peaks(self) -> Dict[str, Any]:
        if self._roofline_peaks is None:
            import jax

            dev = jax.devices()[0]
            fr = peak_flops_record(dev)
            br = peak_bytes_per_s_record(dev)
            self._roofline_peaks = {
                "peak_flops": fr.get("peak_flops"),
                "peak_bytes_per_s": br.get("peak_bytes_per_s"),
                "basis": str(br.get("peak_bytes_per_s_basis") or ""),
                "device_kind": str(getattr(dev, "device_kind", "")),
                "n_devices": int(jax.device_count()),
            }
        return self._roofline_peaks

    def register_roofline(
        self,
        name: str,
        lowered: Any = None,
        cost: Optional[Dict[str, float]] = None,
        role: str = "learner",
        track_grad_rate: bool = False,
    ) -> Optional[Dict[str, Any]]:
        """Register a jitted fn's XLA cost (flops + bytes_accessed, from
        `jit(...).lower(...)` or a precomputed cost dict) and emit its
        roofline verdict. With ``track_grad_rate=True`` the verdict is
        re-emitted each log interval with the measured grad-step rate as
        `calls_per_s` — the attained-fraction-of-roof series for the train
        step. Returns the emitted record (None when the cost analysis
        lacked either axis)."""
        if not self.enabled:
            return None
        if cost is None and lowered is not None:
            cost = cost_of_lowered(lowered)
        if not cost:
            return None
        peaks = self._peaks()
        rec = roofline_record(
            name,
            cost,
            peak_flops=peaks.get("peak_flops"),
            peak_bytes_per_s=peaks.get("peak_bytes_per_s"),
            n_devices=peaks.get("n_devices", 1),
            device_kind=peaks.get("device_kind", ""),
            basis=peaks.get("basis", ""),
            role=role,
        )
        if rec is None:
            return None
        self._rooflines[str(name)] = {
            "cost": dict(cost),
            "role": str(role),
            "track_grad_rate": bool(track_grad_rate),
        }
        self._emit(rec)
        return rec

    def _emit_tracked_rooflines(self, policy_step: int, calls_per_s: float) -> None:
        if calls_per_s <= 0:
            return
        peaks = self._peaks()
        for name, info in self._rooflines.items():
            if not info.get("track_grad_rate"):
                continue
            rec = roofline_record(
                name,
                info["cost"],
                peak_flops=peaks.get("peak_flops"),
                peak_bytes_per_s=peaks.get("peak_bytes_per_s"),
                calls_per_s=calls_per_s,
                n_devices=peaks.get("n_devices", 1),
                device_kind=peaks.get("device_kind", ""),
                basis=peaks.get("basis", ""),
                role=info["role"],
            )
            if rec is not None:
                rec["step"] = int(policy_step)
                self._emit(rec)

    # -- health snapshots --------------------------------------------------
    def xla_health(self) -> Dict[str, Any]:
        now = _xla.compile_counters()
        out: Dict[str, Any] = {
            "compile_count": now["compile_count"] - self._xla0["compile_count"],
            "compile_seconds": round(now["compile_seconds"] - self._xla0["compile_seconds"], 4),
            "jaxpr_traces": now["jaxpr_trace_count"] - self._xla0["jaxpr_trace_count"],
            "compiles_in_interval": now["compile_count"] - self._xla_last["compile_count"],
            "retraces": self.detector.retrace_count() - self._retrace0,
            # persistent-compilation-cache accounting (per-run deltas): a
            # hit is a backend compile some earlier run already paid for
            "cache_hits": int(now.get("cache_hits", 0) - self._xla0.get("cache_hits", 0)),
            "cache_misses": int(now.get("cache_misses", 0) - self._xla0.get("cache_misses", 0)),
        }
        self._xla_last = now
        # per-function compile-seconds breakdown (worst offenders named):
        # this run's delta against the setup-time snapshot, heaviest first
        breakdown: Dict[str, Dict[str, float]] = {}
        for tag, slot in _xla.compile_breakdown().items():
            base = self._breakdown0.get(tag, {"count": 0, "seconds": 0.0})
            count = int(slot["count"] - base["count"])
            if count > 0:
                breakdown[tag] = {
                    "count": count,
                    "seconds": round(slot["seconds"] - base["seconds"], 4),
                }
        if breakdown:
            out["compile_breakdown"] = dict(
                sorted(breakdown.items(), key=lambda kv: -kv[1]["seconds"])[:8]
            )
        attribution = self.detector.attribution()
        if len(attribution) > self._attr_seen:
            out["retrace_attribution"] = attribution[self._attr_seen :]
            self._attr_seen = len(attribution)
        return out

    # -- the log interval --------------------------------------------------
    def log(self, policy_step: int, extra_metrics: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """Flush one log interval: drain spans + aggregator, compute SPS /
        grad-SPS / MFU, snapshot XLA health + device memory, and write every
        sink. Always drains (so disabled/rank>0 loops don't accumulate);
        only writes sinks when active."""
        spans = self.tracker.compute(reset=True)
        metrics = self.aggregator.compute()
        self.aggregator.reset()
        tp = self.throughput.mark(int(policy_step))
        if not self.enabled:
            return {}
        if extra_metrics:
            metrics = {**metrics, **{k: float(v) for k, v in extra_metrics.items()}}
        interval_steps = tp.pop("interval_steps", 0)
        tp_seconds = tp.pop("interval_seconds", 0.0)
        xla_health = self.xla_health()
        # host RSS always + HBM stats where the backend has them: on
        # CPU-only containers device_memory_stats() is {} and the log
        # record used to carry no memory fields at all
        memory = memory_snapshot()
        self._last_step = int(policy_step)

        scalars: Dict[str, float] = dict(metrics)
        scalars["Time/sps"] = tp["sps"]
        if tp.get("grad_steps_per_s"):
            scalars["Time/grad_steps_per_s"] = tp["grad_steps_per_s"]
        if tp.get("replay_ratio") is not None:
            scalars["Time/replay_ratio"] = tp["replay_ratio"]
        if tp.get("mfu") is not None:
            scalars["Time/mfu"] = tp["mfu"]
        for name, secs in spans.items():
            scalars[name] = secs
        # historical derived metrics, kept under their original names
        train_t = spans.get("Time/train_time")
        if train_t and interval_steps > 0:
            scalars["Time/sps_train"] = interval_steps / train_t
        env_t = spans.get("Time/env_interaction_time")
        if env_t and interval_steps > 0:
            scalars["Time/sps_env_interaction"] = interval_steps / env_t
        for key in ("compile_count", "compile_seconds", "retraces"):
            scalars[f"XLA/{key}"] = float(xla_health.get(key) or 0)
        for key, val in memory.items():
            scalars[f"Memory/{key}"] = float(val)

        if self.logger is not None and self.rank == 0:
            self.logger.log_metrics(scalars, int(policy_step))

        rec: Dict[str, Any] = {
            "event": "log",
            "step": int(policy_step),
            "t": round(time.time(), 3),
            "sps": round(tp["sps"], 4),
            "interval_steps": int(interval_steps),
            "interval_seconds": round(tp_seconds, 4),
            "metrics": {k: round(float(v), 6) for k, v in metrics.items()},
            "spans": {k: round(v, 6) for k, v in spans.items()},
            "throughput": {k: round(float(v), 6) for k, v in tp.items()},
            "xla": xla_health,
            "memory": memory,
        }
        self._emit(rec)
        # tracked rooflines (the train step): refine the verdict with this
        # interval's measured grad-step rate → attained fraction of roof
        self._emit_tracked_rooflines(int(policy_step), float(tp.get("grad_steps_per_s") or 0.0))
        if self.rank == 0:  # startup prints per host; interval lines rank-0 only
            self.heartbeat.log(int(policy_step), {**tp, "xla": xla_health, "memory": memory})
        return rec

    # -- shutdown ----------------------------------------------------------
    def close(self, policy_step: int = 0) -> None:
        if self._closed:
            return
        self._closed = True
        if self._step_ann is not None:
            self._exit_step_ann()
        if self._tracing:
            try:
                import jax.profiler as prof

                prof.stop_trace()
            except Exception:
                pass
            self._tracing = False
        if self._mem_sampler is not None:
            # the closing sample pins the run's memory high-water on stream
            self._mem_sampler.stop()
            self._mem_sampler = None
        if self.enabled:
            self._emit(
                {
                    "event": "shutdown",
                    "step": int(policy_step),
                    "xla": self.xla_health(),
                    "spans": self.tracker.compute(),
                    "total_grad_steps": self.throughput.total_grad_steps,
                }
            )
        if self._prom_server is not None:
            self._prom_server.stop()
            self._prom_server = None
            self.prom = None
        if self._live_path is not None:
            try:
                os.remove(self._live_path)  # the endpoint just went away
            except OSError:
                pass
            self._live_path = None
        self.live = None
        if self.jsonl is not None:
            self.jsonl.close()
            self.jsonl = None
