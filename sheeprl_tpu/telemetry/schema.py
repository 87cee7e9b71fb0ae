"""The telemetry event schema: one JSON object per line (JSONL).

Every record is ``{"event": <type>, ...}``. The same schema covers in-run
telemetry (`telemetry.jsonl` in the run's log dir), the TensorBoard-less
metric fallback, and the BENCH_*.json artifacts the bench driver emits — one
machine-readable format end to end.

`validate_event` is deliberately dependency-free (no jsonschema): required
keys + type checks per event type, unknown extra keys allowed (forward
compatible).
"""
from __future__ import annotations

import json
import numbers
from typing import Any, Dict, List, Tuple

SCHEMA_VERSION = 1

_NUM = numbers.Number
_STR = str
_DICT = dict

# event type → {field: (required, type)}
EVENT_SCHEMAS: Dict[str, Dict[str, Tuple[bool, type]]] = {
    # emitted once at Telemetry.setup: the record that makes cpu-fallback
    # impossible to miss. Per-process streams (fleet workers, gateway
    # replicas — telemetry/tracing.py open_process_stream) reuse it as
    # their heartbeat with role/pid/incarnation stamped, so a merged run
    # can attribute every stream to a process identity.
    "startup": {
        "platform": (True, _STR),
        "device_kind": (True, _STR),
        "devices": (True, _NUM),
        "rank": (True, _NUM),
        "world_size": (False, _NUM),
        "algo": (False, _STR),
        "run_name": (False, _STR),
        "schema_version": (False, _NUM),
        "role": (False, _STR),  # worker | replica | learner | gateway
        "pid": (False, _NUM),
        "incarnation": (False, _NUM),
        "worker": (False, _NUM),
        "replica": (False, _NUM),
        # host RSS at startup: every heartbeat carries a memory datum even
        # on CPU-only backends where device_memory_stats() is empty
        "rss_bytes": (False, _NUM),
    },
    # one per log interval
    "log": {
        "step": (True, _NUM),
        "sps": (False, _NUM),
        "metrics": (False, _DICT),
        "spans": (False, _DICT),
        "xla": (False, _DICT),
        "memory": (False, _DICT),
        "throughput": (False, _DICT),
    },
    # end-of-run summary
    "shutdown": {
        "step": (True, _NUM),
        "xla": (False, _DICT),
        "spans": (False, _DICT),
        "total_grad_steps": (False, _NUM),
    },
    # TensorBoardLogger fallback stream (satellite: metrics never dropped)
    "metrics": {
        "step": (True, _NUM),
        "metrics": (True, _DICT),
    },
    # bench driver records (BENCH_*.json contract: metric/value/unit/
    # vs_baseline; platform/device_kind/wall_capped/mfu ride along)
    "bench": {
        "binding_stage": (False, _STR),  # offline trace attribution (informational)
        "metric": (True, _STR),
        "value": (True, _NUM),
        "unit": (True, _STR),
        "vs_baseline": (True, _NUM),
        "platform": (False, _STR),
        "device_kind": (False, _STR),
        "wall_capped": (False, bool),
        "mfu": (False, _NUM),
        "preflight_attempts": (False, _NUM),
        # run-wide memory high-waters (informational context for the
        # real-TPU rounds, not gated — like binding_stage)
        "peak_rss_bytes": (False, _NUM),
        "device_peak_bytes": (False, _NUM),
    },
    # bench pacing/diagnostic lines (stderr)
    "bench_progress": {
        "msg": (True, _STR),
    },
    # windowed profiler capture markers — both the in-loop cadence captures
    # (metric.telemetry.trace_every) and the on-demand remote captures
    # (RemoteProfiler: replica POST /admin/profile, fleet CTRL_PROFILE)
    "trace": {
        "step": (True, _NUM),
        "action": (True, _STR),  # started | stopped
        "trace_dir": (False, _STR),
        "role": (False, _STR),
        "worker": (False, _NUM),
        "replica": (False, _NUM),
    },
    # one distributed-tracing span (telemetry/tracing.py span_record): a
    # named stage of a request or training-round critical path, stamped
    # with W3C-width trace/span ids and wall-clock bounds. Per-process
    # streams each carry their own side's spans; diag/trace.py joins them
    # on trace_id into cross-process paths. `name` and `role` are LABELS
    # (Prometheus stage_latency_ms + report rows) — literal at every emit
    # site, enforced by the telemetry-schema-drift lint rule.
    "trace_span": {
        "name": (True, _STR),
        "role": (True, _STR),  # worker | learner | player | gateway | replica
        "trace_id": (True, _STR),
        "span_id": (True, _STR),
        "t_start": (True, _NUM),
        "t_end": (True, _NUM),
        "dur_ms": (True, _NUM),
        "parent_id": (False, _STR),
        "step": (False, _NUM),
        "seq": (False, _NUM),
        "version": (False, _NUM),
        "worker": (False, _NUM),
        "replica": (False, _NUM),
        "session_id": (False, _STR),
        "detail": (False, _STR),
    },
    # clock-offset handshake (telemetry/tracing.py clock_record): the
    # coordinator's probe send time vs this process's receive time.
    # offset_s upper-bounds the inter-process clock skew; the trace merger
    # subtracts it (when above its skew_min_s floor) before aligning
    # streams on one time axis.
    "clock": {
        "role": (True, _STR),
        "t_send": (True, _NUM),
        "t_recv": (True, _NUM),
        "offset_s": (True, _NUM),
        "worker": (False, _NUM),
        "replica": (False, _NUM),
    },
    # policy-serving stat snapshot (serve/batcher.py): queue depth, batch
    # occupancy, latency percentiles, retrace/reload counters
    "serve": {
        "requests": (True, _NUM),
        "completed": (False, _NUM),
        "rejected": (False, _NUM),
        "errors": (False, _NUM),
        "evictions": (False, _NUM),
        "expired": (False, _NUM),
        "batches": (False, _NUM),
        "queue_depth": (False, _NUM),
        "batch_occupancy": (False, _NUM),
        "avg_batch_size": (False, _NUM),
        "p50_ms": (False, _NUM),
        "p95_ms": (False, _NUM),
        "p99_ms": (False, _NUM),
        "retraces": (False, _NUM),
        "reloads": (False, _NUM),
        "params_version": (False, _NUM),
        "sessions": (False, _NUM),
        # padded-row fraction of dispatched buckets (mean over batches):
        # (bucket - rows)/bucket — the batching-efficiency complement of
        # batch_occupancy, also a Prometheus histogram
        "pad_waste": (False, _NUM),
    },
    # checkpoint hot-reload attempts (serve/reload.py)
    "reload": {
        "action": (True, _STR),  # swapped | failed
        "path": (False, _STR),
        "step": (False, _NUM),
        "params_version": (False, _NUM),
        "error": (False, _STR),
    },
    # cooperative preemption lifecycle (resilience/preemption.py + guard.py)
    "preempt": {
        "step": (True, _NUM),
        "action": (True, _STR),  # requested | checkpointed | flush_timeout
        "signal": (False, _STR),
        "grace_s": (False, _NUM),
    },
    # async checkpoint writer (resilience/ckpt_async.py): block_ms is the
    # train-thread cost, write_ms the background durable-write cost — the
    # pair the acceptance timing test compares against a sync save
    "ckpt_async": {
        "action": (True, _STR),  # enqueued | written | failed
        "step": (True, _NUM),
        "block_ms": (False, _NUM),
        "write_ms": (False, _NUM),
        "bytes": (False, _NUM),
        "path": (False, _STR),
        "in_flight": (False, _NUM),
        "mode": (False, _STR),  # async | sync
    },
    # jittered-backoff retry of a transient op (resilience/supervisor.py)
    "retry": {
        "op": (True, _STR),
        "attempt": (True, _NUM),
        "error": (False, _STR),
        "sleep_s": (False, _NUM),
    },
    # stalled-progress watchdog firings (resilience/supervisor.py);
    # `incident` is the run-monotonic incident counter, `trace_dir` the
    # per-incident profiler dump directory (unique — repeated stalls in one
    # run never overwrite an earlier trace)
    "watchdog": {
        "action": (True, _STR),  # stall | preempt
        "step": (False, _NUM),
        "stalled_s": (False, _NUM),
        "trace_dir": (False, _STR),
        "incident": (False, _NUM),
    },
    # overlapped player/learner engine interval stats (engine/overlap.py):
    # stall split, queue occupancy and the bounded-staleness high-water mark.
    # `step` is the LEARNER's acknowledged env-step counter; `player_step`
    # the PLAYER's produced counter at emit time — the pair lets diag
    # correlate player and learner spans on one step axis (their difference
    # is the in-queue lead, bounded by queue_cap packets)
    "overlap": {
        "step": (True, _NUM),
        "player_step": (False, _NUM),
        "queue_depth": (False, _NUM),
        "queue_cap": (False, _NUM),
        "packets": (False, _NUM),
        "bursts": (False, _NUM),
        "env_steps_ahead": (False, _NUM),
        "player_busy_s": (False, _NUM),
        "player_stall_s": (False, _NUM),
        "learner_stall_s": (False, _NUM),
        "player_stall_frac": (False, _NUM),
        "staleness_max": (False, _NUM),
        "interval_s": (False, _NUM),
    },
    # size-bounded JSONL rotation marker (telemetry/sinks.py): first line of
    # each new segment after the previous one rolled to `<path>.<segment>`
    # (monotonic index — lower is older; diag readers rely on the order)
    "rotate": {
        "segment": (True, _NUM),
        "path": (False, _STR),
        "bytes": (False, _NUM),
    },
    # actor-fleet supervision stream (sheeprl_tpu/fleet/): `action` is
    # either a discrete incident (spawn | respawn | crash | hang | torn_packet
    # | stale_packet | quarantine | drain) with per-worker fields, or "interval" — the
    # periodic liveness snapshot (alive/quarantined counts, cumulative
    # respawns/crashes/hangs/torn packets, queue-depth high-water,
    # round-merge wait). `dropped_steps` counts env steps that never landed
    # learner-side (incomplete trailing rounds at drain, discarded salvage).
    "fleet": {
        "action": (True, _STR),
        "step": (True, _NUM),
        "worker": (False, _NUM),
        "incarnation": (False, _NUM),
        "pid": (False, _NUM),
        "exitcode": (False, _NUM),
        "fails_in_window": (False, _NUM),
        "detail": (False, _STR),
        "workers": (False, _NUM),
        "alive": (False, _NUM),
        "quarantined": (False, _NUM),
        "respawns": (False, _NUM),
        "crashes": (False, _NUM),
        "hangs": (False, _NUM),
        "torn_packets": (False, _NUM),
        "rounds": (False, _NUM),
        "queue_depth_max": (False, _NUM),
        "env_steps": (False, _NUM),
        # shutdown drain accounting: packets in trailing PARTIAL rounds
        # that could not be applied (dropped and counted, never silent) +
        # the env steps they carried
        "drain_dropped": (False, _NUM),
        "dropped_steps": (False, _NUM),
        "round_wait_s": (False, _NUM),
        "interval_s": (False, _NUM),
        # socket-transport link totals on the interval snapshot
        "reconnects": (False, _NUM),
        "dup_frames": (False, _NUM),
        "disconnects": (False, _NUM),
        # learner-side relay drops (telemetry batches the learner's bounded
        # buffer shed; worker-side drops ride each worker's `relay` events)
        "relay_dropped": (False, _NUM),
        # batched-inference act service (fleet/act_service.py), present on
        # interval snapshots when fleet.act_mode=inference: request/batch
        # totals, mean bucket occupancy and pad-waste fraction, live
        # recurrent-state session rows, and the acting publication version
        # (the act_service_starvation finding reads occupancy)
        "act_mode": (False, _STR),
        "act_requests": (False, _NUM),
        "act_batches": (False, _NUM),
        "act_occupancy": (False, _NUM),
        "act_pad_waste": (False, _NUM),
        "act_sessions": (False, _NUM),
        "act_version": (False, _NUM),
    },
    # socket-transport link lifecycle (sheeprl_tpu/fleet/net.py): learner
    # events (listen | accept | reconnect | refuse | disconnect | resync |
    # dup_frame | gap_resend | write_timeout | pull) on the run stream,
    # worker events (connect | connect_backoff | disconnect | resend |
    # partition | chaos_reset | refused) on the worker's own stream.
    # `doctor` folds reconnect storms into the `link_flap` finding and
    # Prometheus mirrors every action as `sheeprl_net_<action>_total`.
    "net": {
        "action": (True, _STR),
        "worker": (False, _NUM),
        "incarnation": (False, _NUM),
        "seq": (False, _NUM),
        "version": (False, _NUM),
        "count": (False, _NUM),
        "bytes": (False, _NUM),
        "detail": (False, _STR),
    },
    # externalized session broker (sheeprl_tpu/gateway/wal.py + brokerd.py +
    # broker_client.py): `action` is either a discrete incident — daemon
    # side: listen | accept | refuse | standby_attach | standby_detach |
    # tail_attach | sync_failed | promote (standby took over; promotion_s =
    # seconds past the last heartbeat) | fenced (a zombie primary's late
    # write rejected by the fencing epoch) | demote | zombie | repl_timeout;
    # WAL side: wal_torn_tail (recovery truncated a torn record) |
    # wal_rehydrate (LRU-evicted-but-durable session re-read from the log) |
    # rehydrate_failed | compact; client side: client_reconnect |
    # client_failover | client_partition — or "interval", the periodic
    # daemon snapshot (sessions, replication lag high-water, sync-wait and
    # WAL-fsync p95s). Prometheus mirrors every action as
    # `sheeprl_broker_<action>_total`; doctor folds the stream into the
    # broker_failover and broker_lag findings.
    "broker": {
        "action": (True, _STR),
        "role": (False, _STR),  # primary | standby | demoted
        "epoch": (False, _NUM),  # the fencing token
        "seq": (False, _NUM),  # WAL sequence number
        "version": (False, _NUM),
        "sessions": (False, _NUM),
        "puts": (False, _NUM),
        "gets": (False, _NUM),
        "fenced_writes": (False, _NUM),
        "standbys": (False, _NUM),
        "lag": (False, _NUM),  # replication lag high-water (records)
        "count": (False, _NUM),
        "bytes": (False, _NUM),
        "promotion_s": (False, _NUM),
        "repl_wait_p95_ms": (False, _NUM),
        "fsync_p95_ms": (False, _NUM),
        "detail": (False, _STR),
    },
    # one served step captured by the data flywheel (sheeprl_tpu/flywheel/
    # capture.py): written to the replica's OWN capture segments
    # (<capture_dir>/replica_NNN/capture.jsonl, JsonlSink rotation), NOT the
    # telemetry stream — but it shares this schema so capture files are
    # validated and torn-tail tolerant the same way. `step` is the
    # per-session capture counter on this replica incarnation (the dedup
    # axis ingest uses), `trace_id` the PR-10 join key back to the gateway
    # request, `params_version` the policy version that produced the action
    # (the staleness axis the fine-tune recipe filters on). `obs` is the
    # raw numeric observation tree and `actions` the [1, ...] action row —
    # numbers only, never free-form client fields (the PII boundary).
    "capture": {
        "session_id": (True, _STR),
        "step": (True, _NUM),
        "obs": (True, _DICT),
        "actions": (True, list),
        "params_version": (True, _NUM),
        "trace_id": (False, _STR),
        "replica": (False, _NUM),
        "incarnation": (False, _NUM),
        "deterministic": (False, bool),
        "reward": (False, _NUM),
        "done": (False, bool),
        "t": (False, _NUM),
    },
    # data-flywheel lifecycle (sheeprl_tpu/flywheel/): `action` is
    # capture_interval (periodic capture-writer snapshot on the replica's
    # stream: captured/skipped/bytes), ingest (offline segment replay into
    # the replay buffer: samples/duplicates/torn_lines + the
    # params_version spread and its lag vs the serving version — what the
    # doctor's flywheel_staleness finding reads), dropped_stale (samples
    # the recipe refused for exceeding max_version_lag), finetune (one
    # gradient burst), reload (the new checkpoint pushed through the
    # gateway's rolling reload). Prometheus mirrors actions as
    # `sheeprl_flywheel_<action>_total` plus ingest gauges.
    "flywheel": {
        "action": (True, _STR),
        "samples": (False, _NUM),
        "duplicates": (False, _NUM),
        "torn_lines": (False, _NUM),
        "segments": (False, _NUM),
        "captured": (False, _NUM),
        "skipped": (False, _NUM),
        "bytes": (False, _NUM),
        "dropped_stale": (False, _NUM),
        "samples_per_s": (False, _NUM),
        "unrewarded_tails": (False, _NUM),
        "version_min": (False, _NUM),
        "version_max": (False, _NUM),
        "serving_version": (False, _NUM),
        "version_lag": (False, _NUM),
        "steps": (False, _NUM),
        "step": (False, _NUM),
        "params_version": (False, _NUM),
        "replica": (False, _NUM),
        "loss": (False, _NUM),
        "detail": (False, _STR),
        "t": (False, _NUM),
    },
    # one partition-spec inference decision (sheeprl_tpu/parallel/sharding.py
    # SpecEngine): `action` is "leaf" — one parameter/optimizer-state leaf's
    # inferred PartitionSpec, the rule that produced it, the reason chain
    # (divisibility fallbacks included) and its bytes/bytes-per-chip — or
    # "summary", the per-tree totals (`bytes_per_chip` is the number the
    # MULTICHIP bench gates; `replicated_bytes` is what doctor's
    # `replicated_giant` hunts oversized leaves in). dp/fsdp/tp are the mesh
    # axis sizes the decisions were made against.
    "sharding": {
        "action": (True, _STR),  # leaf | summary
        "group": (False, _STR),  # params | opt_state
        "path": (False, _STR),
        "shape": (False, list),
        "spec": (False, _STR),
        "rule": (False, _STR),
        "reason": (False, _STR),
        "bytes": (False, _NUM),
        "bytes_per_chip": (False, _NUM),
        "dp": (False, _NUM),
        "fsdp": (False, _NUM),
        "tp": (False, _NUM),
        "leaves": (False, _NUM),
        "replicated_leaves": (False, _NUM),
        "total_bytes": (False, _NUM),
        "replicated_bytes": (False, _NUM),
    },
    # where the player runs, once at set-up (parallel/placement.py
    # `make_param_mirror`): the device `algo.player.device` resolved to
    # ("tpu:0", "cpu:0"), the bytes of the tree the player reads, the bytes
    # at and over which `auto` keeps it on the learner's accelerator, and
    # whether a refresh stays on the learner's device (1) or not (0)
    "placement": {
        "player_device": (True, _STR),
        "learner_device": (True, _STR),
        "mode": (True, _STR),  # auto | host | accelerator
        "tree_bytes": (True, _NUM),
        "threshold_bytes": (True, _NUM),
        "same_device": (True, _NUM),
        "refresh": (False, _STR),  # alias | copy | transfer
    },
    # a sequence policy on the recurrent on-policy loop, once at set-up
    # (algos/ppo_recurrent/sequence_policy.py): the share of each layer this
    # chip holds, the bytes of the per-env cache and of the parameters, and
    # per leaf of the cache (the latents; or the keys and values) the
    # major-to-minor order it is held in on the device
    "sequence_policy": {
        "backbone": (True, _STR),
        "layers": (True, _NUM),
        "experts_held": (True, _NUM),
        "first_expert": (True, _NUM),
        "n_routed_experts": (True, _NUM),
        "heads_held": (True, _NUM),
        "num_attention_heads": (True, _NUM),
        "vocab_held": (True, _NUM),
        "vocab_size": (True, _NUM),
        "cache_bytes": (True, _NUM),
        "param_bytes": (True, _NUM),
        "cache_layout": (True, _DICT),
    },
    # a block-diffusion policy on the same loop, once at set-up
    # (algos/ppo_recurrent/block_diffusion_policy.py): the ids an env step
    # commits, the forwards it takes for them, the ids a forward commits for
    # all envs, the rows one gradient step's forward carries (the clean
    # sequences and their denoising copies), the id that stands for a mask,
    # the form of the update's attention (`kernel` on a TPU, `xla` elsewhere)
    # and, with the kernel, the share of its (row tile, key tile) pairs the
    # static mask leaves non-empty for one sequence
    "block_diffusion": {
        "block_length": (True, _NUM),
        "denoising_steps": (True, _NUM),
        "tokens_per_forward": (True, _NUM),
        "update_tokens": (True, _NUM),
        "mask_id": (True, _NUM),
        "train_attention": (True, _STR),
        "attn_blocks_computed": (False, _NUM),
    },
    # the expert layers' load over one train call, from numbers the update
    # returns beside its losses: (token, expert) pairs computed here, the
    # rows the grouped products multiplied (the buffer each layer and step
    # took: a slot for every pair that can come, or `load_rows`, about
    # twice a uniform router's load, where the update sizes it so), their
    # ratio, the fullest held expert's load over the mean (the worst layer
    # and step), the pairs left out (none: dropless) and the (layer,
    # gradient step) pairs whose load did not fit `load_rows` and took the
    # worst case's buffer
    "moe_load": {
        "routed_here": (True, _NUM),
        "rows": (True, _NUM),
        "slot_occupancy": (True, _NUM),
        "max_over_mean": (True, _NUM),
        "dropped": (True, _NUM),
        "fallbacks": (True, _NUM),
    },
    # how a device replay ring keeps its rows, once per ring when it is
    # allocated (data/device_ring.py `_allocate`): per key the item's own
    # (`logical`) and its `stored` shape, dtype and bytes, and the share of
    # the ring's bytes stored row-contiguous (`stored_item_shape`'s rule)
    "ring_layout": {
        "device": (True, _STR),
        "rows": (True, _NUM),
        "n_envs": (True, _NUM),
        "keys": (True, _DICT),
        "total_bytes": (True, _NUM),
        "contiguous_bytes": (True, _NUM),
        "contiguous_bytes_share": (True, _NUM),
    },
    # what the world model's sequence scan takes out of its backward loop,
    # once when the train function is traced (ops/wgrad_hoist.py `scan`,
    # dreamer_v3.py `make_train_fn`): which scan, how many Dense kernels'
    # gradients are one matmul after the backward scan, their float32 bytes
    # (what that scan no longer carries and rewrites at every step) and the
    # rows (T * B) of each contraction
    "wgrad_hoist": {
        "scan": (True, _STR),  # coupled | decoupled
        "kernels": (True, _NUM),
        "kernel_bytes": (True, _NUM),
        "rows": (True, _NUM),
    },
    # deterministic fault injection (resilience/chaos.py): faults the
    # SUPERVISOR injects (worker-side faults surface as `fleet` incidents —
    # a chaos crash is indistinguishable from a real one by design)
    "chaos": {
        "fault": (True, _STR),  # dropped_publication | armed
        "worker": (False, _NUM),
        "seq": (False, _NUM),
        "detail": (False, _STR),
    },
    # a run restored from a checkpoint (resilience/guard.py)
    "resume": {
        "step": (True, _NUM),
        "checkpoint": (False, _STR),
        "run_dir": (False, _STR),
        "fingerprint": (False, _STR),
    },
    # per-session lifecycle incidents on the serve stream (serve/batcher.py):
    # `evicted` = a live session's latent fell off the LRU (the next request
    # gets 410 unless re-hydrated)
    "session": {
        "action": (True, _STR),  # evicted
        "session_id": (False, _STR),
        "detail": (False, _STR),
    },
    # serving-replica supervision stream (sheeprl_tpu/gateway/replica.py):
    # spawn | respawn | ready (port bound) | crash | hang | quarantine |
    # drain | reload — the serving analogue of the `fleet` incident events
    "replica": {
        "action": (True, _STR),
        "replica": (False, _NUM),
        "incarnation": (False, _NUM),
        "pid": (False, _NUM),
        "port": (False, _NUM),
        "fails_in_window": (False, _NUM),
        "params_version": (False, _NUM),
        "detail": (False, _STR),
    },
    # gateway stat snapshot (sheeprl_tpu/gateway/gateway.py): request/ack/
    # shed/failover counters, end-to-end latency percentiles, fleet liveness
    # and admission-controller occupancy — the multi-replica analogue of the
    # `serve` record
    "gateway": {
        "requests": (True, _NUM),
        "acked": (False, _NUM),
        "errors": (False, _NUM),
        "failovers": (False, _NUM),
        "migrations": (False, _NUM),
        "rehydrates": (False, _NUM),
        "expired": (False, _NUM),
        "lost": (False, _NUM),
        "retries": (False, _NUM),
        "broker_unavailable": (False, _NUM),
        "p50_ms": (False, _NUM),
        "p95_ms": (False, _NUM),
        "p99_ms": (False, _NUM),
        "replicas": (False, _NUM),
        "routable": (False, _NUM),
        "quarantined": (False, _NUM),
        "respawns": (False, _NUM),
        "sessions": (False, _NUM),
        "broker_sessions": (False, _NUM),
        "admission_inflight": (False, _NUM),
        "admission_admitted": (False, _NUM),
        "admission_shed": (False, _NUM),
        "admission_shed_low": (False, _NUM),
        "admission_tokens": (False, _NUM),
    },
    # serving load-bench record (scripts/bench_serve.py -> SERVE_r*.json):
    # latency percentiles + shed rate + failover recovery, gated run-over-run
    # by scripts/bench_compare.py with lower-is-better direction
    "serve_bench": {
        "binding_stage": (False, _STR),  # offline trace attribution (informational)
        "metric": (True, _STR),
        "value": (True, _NUM),
        "unit": (True, _STR),
        "vs_baseline": (True, _NUM),
        "direction": (False, _STR),  # lower | higher (gate direction)
        "p50_ms": (True, _NUM),
        "p95_ms": (True, _NUM),
        "p99_ms": (True, _NUM),
        "shed_rate": (True, _NUM),
        "error_rate": (False, _NUM),
        "requests": (False, _NUM),
        "acked": (False, _NUM),
        "throughput_rps": (False, _NUM),
        "sessions": (False, _NUM),
        "replicas": (False, _NUM),
        "concurrency": (False, _NUM),
        "duration_s": (False, _NUM),
        "failover": (False, _DICT),  # {killed_replica, recovery_s, acked_loss}
        "platform": (False, _STR),
        # per-stage latency breakdown from the trace-context timing the
        # driver requests (traceparent on every bench request): full
        # percentiles per stage in `stages`, plus flattened p95s for the
        # stages bench_compare.py gates with the lower-is-better direction
        "stages": (False, _DICT),  # {stage: {p50_ms, p95_ms, p99_ms}}
        "stage_forward_p95_ms": (False, _NUM),
        "stage_jit_step_p95_ms": (False, _NUM),
        "stage_batch_queue_p95_ms": (False, _NUM),
        # broker-failover leg (--broker external): the externalized-broker
        # topology and what the mid-load SIGKILL of the primary cost.
        # `broker` holds {mode, durability, killed, promotion_s, recovery_s,
        # repl_lag_p95_ms, acked_loss}; the flattened fields are what
        # bench_compare.py gates (recovery/lag lower-is-better, acked_loss
        # absolutely zero).
        "broker": (False, _DICT),
        "broker_recovery_s": (False, _NUM),
        "broker_repl_lag_p95_ms": (False, _NUM),
        # driver-process memory high-waters (informational, like binding_stage)
        "peak_rss_bytes": (False, _NUM),
        "device_peak_bytes": (False, _NUM),
    },
    # data-flywheel end-to-end bench record (scripts/bench_flywheel.py ->
    # FLYWHEEL_r*.json): one full serve -> capture -> ingest -> fine-tune ->
    # rolling-reload -> serve-again round. The headline `value` is ingest
    # samples/sec (direction: higher); `capture_act_p95_ms` is the act p95
    # WITH capture enabled and `capture_overhead_frac` its fractional cost
    # vs the capture-off baseline (both lower-is-better, gated by
    # bench_compare.py); `reload_to_fresh_act_s` is the lag from the
    # rolling-reload trigger to the first acked act served by the bumped
    # params_version; `trace_join_frac` is the fraction of ingested samples
    # that joined back to a capture trace id (must be 1.0); `acked_loss`
    # counts counter-continuity mismatches across the reload (invariant 0).
    "flywheel_bench": {
        "binding_stage": (False, _STR),  # offline trace attribution (informational)
        "metric": (True, _STR),
        "value": (True, _NUM),
        "unit": (True, _STR),
        "vs_baseline": (True, _NUM),
        "direction": (False, _STR),
        "ingest_samples_per_s": (True, _NUM),
        "capture_act_p95_ms": (True, _NUM),
        "baseline_act_p95_ms": (True, _NUM),
        "capture_overhead_frac": (True, _NUM),
        "reload_to_fresh_act_s": (True, _NUM),
        "trace_join_frac": (True, _NUM),
        "acked_loss": (True, _NUM),
        "ingested": (False, _NUM),
        "duplicates": (False, _NUM),
        "torn_lines": (False, _NUM),
        "dropped_stale": (False, _NUM),
        "finetune_steps": (False, _NUM),
        "params_version_served": (False, _NUM),
        "sessions": (False, _NUM),
        "replicas": (False, _NUM),
        "requests": (False, _NUM),
        "acked": (False, _NUM),
        "duration_s": (False, _NUM),
        "platform": (False, _STR),
        # driver-process memory high-waters (informational, like binding_stage)
        "peak_rss_bytes": (False, _NUM),
        "device_peak_bytes": (False, _NUM),
    },
    # cadenced memory sample (telemetry/memory.py MemorySampler): host RSS
    # always — the CPU container must still grow a watermark series — plus
    # device HBM stats when the backend reports them and an optional
    # live-buffer census. Emitted on every process stream (learner, fleet
    # workers, replicas, brokerd; relayed like any other event), read by
    # doctor's hbm_pressure / host_mem_leak findings, `sheeprl_tpu top`'s
    # memory columns and the Prometheus gauges.
    "mem": {
        "role": (True, _STR),
        "rss_bytes": (True, _NUM),
        "t": (False, _NUM),
        "step": (False, _NUM),
        "rss_peak_bytes": (False, _NUM),
        "hbm_bytes_in_use": (False, _NUM),
        "hbm_peak_bytes": (False, _NUM),
        "hbm_bytes_limit": (False, _NUM),
        "live_buffers": (False, _NUM),
        "live_buffer_bytes": (False, _NUM),
        "worker": (False, _NUM),
        "replica": (False, _NUM),
        "index": (False, _NUM),
    },
    # roofline verdict for one jitted fn (telemetry/throughput.py
    # roofline_record): arithmetic intensity (flops / bytes_accessed from
    # XLA cost analysis) against the device's peak-FLOP/s and peak-HBM-
    # bandwidth tables → compute- vs memory-bound, with the attained
    # fraction of the bounding roof once a measured call rate is known.
    # `fn` is a label (Prometheus roofline_attained_frac{fn=...}) — low
    # cardinality by construction: train_step + one name per serve bucket.
    "roofline": {
        "fn": (True, _STR),
        "flops": (True, _NUM),
        "bytes_accessed": (True, _NUM),
        "intensity": (True, _NUM),
        "bound": (True, _STR),  # compute | memory | unknown
        "ridge_intensity": (False, _NUM),
        "peak_flops": (False, _NUM),
        "peak_bytes_per_s": (False, _NUM),
        "attained_frac": (False, _NUM),
        "attained_flops_per_s": (False, _NUM),
        "calls_per_s": (False, _NUM),
        "device_kind": (False, _STR),
        "basis": (False, _STR),
        "role": (False, _STR),
        "step": (False, _NUM),
        "t": (False, _NUM),
    },
    # relay sink flush accounting (telemetry/relay.py): one per flush
    # cadence on the EMITTING process's own stream. `sent`/`dropped` are
    # cumulative counters — the aggregator keys SLO rules like
    # "relay drops == 0" on the latest value, and doctor can see where
    # backpressure bit without the relayed copy (the drop happened because
    # the relayed copy could not be sent).
    "relay": {
        "role": (True, _STR),
        "sent": (True, _NUM),
        "dropped": (True, _NUM),
        "batches": (True, _NUM),
        "worker": (False, _NUM),
        "replica": (False, _NUM),
        "index": (False, _NUM),
        "detail": (False, _STR),
    },
    # SLO burn alert (diag/aggregator.py): a configured rule
    # (diag.live.slo) breached for at least its burn fraction of the
    # sliding window. `rule` is the configured rule name (a LABEL — the
    # Prometheus mirror is `slo_alerts_total{rule=...}`), `metric` the
    # dotted snapshot path it watches, `value` the observed value that
    # breached and `threshold` the configured bound. Raised alerts land on
    # the aggregator host's main stream so doctor finds them post-hoc.
    "alert": {
        "rule": (True, _STR),
        "state": (True, _STR),  # firing | resolved
        "metric": (True, _STR),
        "value": (False, _NUM),
        "threshold": (False, _NUM),
        "burn_frac": (False, _NUM),
        "window_s": (False, _NUM),
        "severity": (False, _STR),  # critical | warning
        "detail": (False, _STR),
    },
}

# span name → the counts it may carry (keyword arguments of `telem.span` /
# `Span`, or `Span.count` inside). The prefix says whose time it is, because
# a capture's reader names an idle gap of the device by the shortest `Time/`
# span over it on ANY thread: `Time/<phase>` is a phase of the thread that
# feeds the device and tiles that thread's iteration; `Wait/<what>` is a
# thread blocked on the other; `Player/<step>` is a sub-step of the player
# inside `Time/env_interaction_time` (`Player/prepare`, `Player/dispatch` and
# `Player/fetch` tile `Player/act`; `Player/bootstrap` lies in
# `Player/record`). howto/telemetry.md has where each lies.
SPAN_SCHEMAS: Dict[str, Tuple[str, ...]] = {
    "Time/env_interaction_time": ("env_steps", "version"),
    "Time/train_time": ("grad_steps", "burst", "tokens"),
    "Time/learner_apply": ("env_steps", "packets"),
    "Time/replay_sync": ("rows", "bytes"),
    "Time/replay_sample": ("grad_steps",),
    "Time/replay_stage": (),
    "Time/param_refresh": ("bytes", "leaves", "same_device"),
    "Time/log_flush": (),
    "Time/checkpoint": (),
    "Time/cache_reset": ("rows",),
    "Time/update_report": (),
    "Wait/learner_queue": ("packets",),
    "Wait/player_queue": (),
    "Player/act": ("tokens", "cache_rows", "forwards"),
    "Player/prepare": (),
    "Player/dispatch": ("leaves", "h2d_bytes"),
    "Player/fetch": ("arrays", "d2h_bytes"),
    "Player/env_step": (),
    "Player/record": (),
    "Player/bootstrap": ("envs",),
}
SPAN_PREFIXES = ("Time/", "Wait/", "Player/")


def validate_event(rec: Any) -> List[str]:
    """Return a list of problems (empty == valid)."""
    errors: List[str] = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, expected dict"]
    event = rec.get("event")
    if not isinstance(event, str):
        return ["missing 'event' field"]
    schema = EVENT_SCHEMAS.get(event)
    if schema is None:
        return [f"unknown event type {event!r} (known: {sorted(EVENT_SCHEMAS)})"]
    for field, (required, typ) in schema.items():
        if field not in rec:
            if required:
                errors.append(f"{event}: missing required field '{field}'")
            continue
        val = rec[field]
        if typ is _NUM and isinstance(val, bool):
            errors.append(f"{event}: field '{field}' is bool, expected number")
        elif not isinstance(val, typ):
            errors.append(
                f"{event}: field '{field}' is {type(val).__name__}, expected {typ.__name__}"
            )
    return errors


def validate_jsonl(path: Any) -> List[str]:
    """Validate a whole JSONL file; returns per-line problems."""
    errors: List[str] = []
    with open(path) as fh:
        for i, line in enumerate(fh, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as err:
                errors.append(f"line {i}: not JSON ({err})")
                continue
            errors.extend(f"line {i}: {e}" for e in validate_event(rec))
    return errors
