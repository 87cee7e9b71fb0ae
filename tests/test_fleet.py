"""Actor fleet (sheeprl_tpu/fleet/) + chaos harness (resilience/chaos.py).

The invariants, each proved with a deterministic injected fault:

* the packet framing rejects torn frames (CRC) instead of half-applying
  them to the replay buffer;
* the chaos injector is seed/threshold-deterministic and picklable;
* round merging backfills quarantined columns from survivors (fixed-width
  mode) and offsets per-env ops (sliced mode);
* a 512-step SAC fleet run with a worker CRASH and a worker HANG injected
  mid-run completes with the Ratio replay-ratio ledger BIT-IDENTICAL to the
  single-process overlap engine's, and `doctor` reports the injected
  incidents as ranked findings;
* a repeated crasher exhausts the fail budget and is QUARANTINED; the fleet
  degrades gracefully (training completes on the survivors);
* a degraded round (one worker quarantined) is a smaller PACKET: the learner's
  one loop stages the next burst by the round's own `env_steps`;
* a torn packet is detected learner-side and routed through the worker
  fault path;
* SIGTERM mid-run drains live workers into a consistent, resumable final
  checkpoint.
"""
import json
import pickle
import threading
from pathlib import Path

import numpy as np
import pytest

from sheeprl_tpu.fleet import FleetEngine, FleetPacket, FleetRound, TornPacketError
from sheeprl_tpu.fleet.programs import merge_ppo_round
from sheeprl_tpu.fleet.protocol import decode_packet, encode_packet
from sheeprl_tpu.engine import RecordingSink
from sheeprl_tpu.resilience.chaos import ChaosInjector


# ---------------------------------------------------------------------------
# unit: packet framing
# ---------------------------------------------------------------------------
def test_packet_roundtrip_and_torn_detection():
    sink = RecordingSink()
    sink.add({"x": np.zeros((1, 2, 3), np.float32)})
    sink.stat("Rewards/rew_avg", 1.5)
    pkt = FleetPacket(1, 0, 7, 2, 3, sink)
    frame = encode_packet(pkt)
    out = decode_packet(frame)
    assert (out.worker_id, out.seq, out.env_steps, out.version) == (1, 7, 2, 3)
    assert out.payload.ops[0][0] == "add"
    assert out.payload.stats == [("Rewards/rew_avg", 1.5)]

    # flip payload bytes: the CRC must reject, never half-apply
    torn = frame[:-1] + (bytes([frame[-1][0] ^ 0xFF]) + frame[-1][1:],)
    with pytest.raises(TornPacketError):
        decode_packet(torn)
    with pytest.raises(TornPacketError):
        decode_packet(("garbage",))


# ---------------------------------------------------------------------------
# unit: chaos injector
# ---------------------------------------------------------------------------
def test_chaos_injector_is_deterministic_and_picklable():
    chaos = ChaosInjector(0, torn_packet_at=3, torn_workers=[0], seed=11)
    blob = b"x" * 64
    assert chaos.corrupt(blob, 2) == blob  # wrong seq: untouched
    t1 = chaos.corrupt(blob, 3)
    t2 = ChaosInjector(0, torn_packet_at=3, torn_workers=[0], seed=11).corrupt(blob, 3)
    assert t1 != blob and t1 == t2  # corrupted, reproducibly
    # survives the spawn-args pickle
    clone = pickle.loads(pickle.dumps(chaos))
    assert clone.corrupt(blob, 3) == t1

    # targeting: empty worker list defaults to worker 0
    assert ChaosInjector(0, crash_at_step=5).active
    assert ChaosInjector(1, drop_publication_at=2, drop_workers=[1]).drops_publication(2)
    assert not ChaosInjector(0, drop_publication_at=2, drop_workers=[1]).drops_publication(2)


def test_chaos_hang_and_crash_are_incarnation_gated():
    # incarnation 1 (a respawned worker) must NOT re-crash without repeat
    chaos = ChaosInjector(0, crash_at_step=5)
    chaos.incarnation = 1
    chaos.on_step(10)  # would os._exit on incarnation 0
    hang = ChaosInjector(0, hang_at_step=5, hang_s=0.01)
    hang.incarnation = 1
    hang.on_step(10)
    assert not hang._hung


# ---------------------------------------------------------------------------
# unit: round merging
# ---------------------------------------------------------------------------
class _FakeRB:
    def __init__(self):
        self.adds = []

    def add(self, data, idxes=None, validate_args=False):
        self.adds.append((data, idxes))


def _sink_packet(worker_id, value, epw=1):
    sink = RecordingSink()
    sink.add({"observations": np.full((1, epw, 2), value, np.float32)})
    return FleetPacket(worker_id, 0, 0, epw, 1, sink)


def test_apply_concat_merges_in_worker_order_and_backfills_quarantined():
    eng = FleetEngine(enabled=True, workers=3, telem=None)
    eng.num_envs = 3
    eng.envs_per_worker = 1
    rb = _FakeRB()
    # worker 1 quarantined: its column must be backfilled from survivors
    rnd = FleetRound([_sink_packet(0, 0.0), _sink_packet(2, 2.0)], [0, 2], 2)
    assert eng.apply_concat(rnd, rb) == 2  # only REAL steps counted
    merged = rb.adds[0][0]["observations"]
    assert merged.shape == (1, 3, 2)  # full width: jitted shapes never change
    assert merged[0, 0, 0] == 0.0 and merged[0, 2, 0] == 2.0
    assert merged[0, 1, 0] in (0.0, 2.0)  # backfilled from a survivor


def test_apply_sliced_offsets_env_indices_per_worker():
    eng = FleetEngine(enabled=True, workers=2, telem=None)
    eng.num_envs = 4
    eng.envs_per_worker = 2
    sink = RecordingSink()
    sink.add({"x": np.zeros((1, 2, 1), np.float32)})  # full slice
    sink.add({"x": np.ones((1, 1, 1), np.float32)}, [1])  # env 1 OF THE SLICE
    rb = _FakeRB()
    rb.mark_restart = lambda i: rb.adds.append(("restart", i))
    rnd = FleetRound([FleetPacket(1, 0, 0, 2, 1, sink)], [1], 2)
    eng.apply_sliced(rnd, rb)
    assert rb.adds[0][1] == [2, 3]  # worker 1 owns global columns 2-3
    assert rb.adds[1][1] == [3]  # slice-local index 1 → global 3


def test_stale_packets_are_dropped_for_strict_rounds():
    """The PPO strict protocol: after a crash, a salvaged packet plus the
    respawned incarnation's re-produced rollout for the SAME publication
    must not leave the worker's FIFO one publication behind — take_round's
    min_version drops the stale one instead of merging it forever after."""
    from collections import deque

    eng = FleetEngine(enabled=True, workers=2, telem=None)
    eng._pending = {0: deque(), 1: deque()}
    stale = _sink_packet(0, 0.0)._replace(version=1)
    fresh = _sink_packet(0, 1.0)._replace(version=2)
    eng._pending[0].extend([stale, fresh])
    eng._drop_stale(2, step=0)
    assert list(eng._pending[0]) == [fresh]
    assert eng.dropped_steps == stale.env_steps
    eng._drop_stale(2, step=0)  # idempotent: the fresh packet survives
    assert list(eng._pending[0]) == [fresh]


def test_merge_ppo_round_backfills_and_concats():
    def payload(v):
        return ({"rewards": np.full((4, 1, 1), v, np.float32)}, np.full((1, 1), v), [(v, 4.0)])

    rnd = FleetRound(
        [FleetPacket(0, 0, 0, 4, 1, payload(0.0)), FleetPacket(2, 0, 0, 4, 1, payload(2.0))],
        [0, 2],
        8,
    )
    local, next_value, ep_stats = merge_ppo_round(rnd, 3)
    assert local["rewards"].shape == (4, 3, 1) and next_value.shape == (3, 1)
    assert local["rewards"][0, 0, 0] == 0.0 and local["rewards"][0, 2, 0] == 2.0
    assert len(ep_stats) == 2  # backfilled slots don't double-count stats


# ---------------------------------------------------------------------------
# e2e helpers
# ---------------------------------------------------------------------------
def _sac_args(run_name, total=512, extra=()):
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
    from sheeprl_tpu.utils.checkpoint import CheckpointManager

    base = Path("logs/runs/sac/continuous_dummy") / run_name
    cks = sorted(
        (base / "version_0" / "checkpoint").glob("ckpt_*.ckpt"),
        key=lambda p: int(p.stem.split("_")[1]),
    )
    assert cks, f"no checkpoint under {base}"
    return CheckpointManager.load(cks[-1]), base


def _fleet_events(base):
    events = [json.loads(ln) for ln in open(base / "version_0" / "telemetry.jsonl")]
    return events, [e for e in events if e["event"] == "fleet"]


# ---------------------------------------------------------------------------
# e2e: THE acceptance run — crash + hang injected, ledger bit-identical
# ---------------------------------------------------------------------------
def test_chaos_crash_and_hang_ledger_matches_overlap_engine():
    """512 SAC steps through a 2-worker fleet with worker 0 CRASHING (hard
    os._exit) at lifetime step 50 and worker 1 HANGING at step 80 (heartbeat
    watchdog → SIGKILL → respawn). Despite both incidents the Ratio
    env-step:grad-step ledger, cumulative grad steps and buffer fill must be
    BIT-IDENTICAL to the single-process overlap engine's, and `doctor` must
    report the incidents as ranked findings."""
    from sheeprl_tpu.cli import run

    run(
        _sac_args(
            "fleet_chaos",
            extra=[
                "algo.fleet.workers=2",
                # a slice gets fleet.hang_s from its last beat, and a
                # worker's first policy slice holds a jit compile: at 1 s a
                # loaded machine (xdist workers on shared cores) SIGKILLed
                # healthy workers until both were quarantined. 5 s still
                # catches the injected 60 s hang, and nothing else.
                "fleet.hang_s=5.0",
                "resilience.chaos.enabled=True",
                "resilience.chaos.crash_at_step=50",
                "resilience.chaos.crash_workers=[0]",
                "resilience.chaos.hang_at_step=80",
                "resilience.chaos.hang_workers=[1]",
                "resilience.chaos.hang_s=60.0",
            ],
        )
    )
    fleet_st, base = _final_ckpt("fleet_chaos")
    run(_sac_args("fleet_chaos_ref", extra=["algo.overlap.enabled=True"]))
    ref_st, _ = _final_ckpt("fleet_chaos_ref")

    # the ledger: bit-identical accounting despite a death and a hang
    assert fleet_st["policy_step"] == ref_st["policy_step"] == 512
    assert fleet_st["cumulative_grad_steps"] == ref_st["cumulative_grad_steps"] > 0
    assert fleet_st["ratio"] == ref_st["ratio"]
    assert fleet_st["rb"]["pos"] == ref_st["rb"]["pos"]
    assert fleet_st["rb"]["full"] == ref_st["rb"]["full"]

    # both injected incidents are on the telemetry stream, with recovery
    events, fleet_evs = _fleet_events(base)
    actions = [(e["action"], e.get("worker")) for e in fleet_evs]
    assert ("crash", 0) in actions and ("respawn", 0) in actions
    assert ("hang", 1) in actions and ("respawn", 1) in actions
    assert not any(a == "quarantine" for a, _ in actions)  # single faults only
    intervals = [e for e in fleet_evs if e["action"] == "interval"]
    assert intervals and intervals[-1]["respawns"] == 2
    from sheeprl_tpu.telemetry.schema import validate_jsonl

    assert validate_jsonl(base / "version_0" / "telemetry.jsonl") == []

    # doctor: the injected incidents come back as ranked findings
    from sheeprl_tpu.config import Config
    from sheeprl_tpu.diag.findings import run_detectors
    from sheeprl_tpu.diag.timeline import Timeline, iter_events

    tl = Timeline(list(iter_events(base / "version_0" / "telemetry.jsonl")))
    codes = [f.code for f in run_detectors(tl)]
    assert "worker_flap" in codes
    assert "fleet_degraded" in codes

    # the fleet loop never leaks threads into the next test
    assert not [t for t in threading.enumerate() if t.name.startswith("fleet-")]


# ---------------------------------------------------------------------------
# e2e: fail budget → quarantine → graceful degradation
# ---------------------------------------------------------------------------
def test_repeated_crasher_is_quarantined_and_fleet_degrades():
    from sheeprl_tpu.cli import run

    run(
        _sac_args(
            "fleet_quarantine",
            total=96,
            extra=[
                "algo.fleet.workers=2",
                "fleet.max_fails=1",
                "resilience.chaos.enabled=True",
                "resilience.chaos.crash_at_step=10",
                "resilience.chaos.crash_workers=[0]",
                "resilience.chaos.crash_repeat=True",  # every incarnation dies
            ],
        )
    )
    st, base = _final_ckpt("fleet_quarantine")
    # training COMPLETED on the surviving worker, accounting exact over the
    # real steps (96 total; grads owed for steps past learning_starts=16)
    assert st["policy_step"] == 96
    assert st["cumulative_grad_steps"] == 80

    events, fleet_evs = _fleet_events(base)
    actions = [e["action"] for e in fleet_evs]
    assert actions.count("crash") == 2  # original + one respawned incarnation
    assert "quarantine" in actions
    quarantine = next(e for e in fleet_evs if e["action"] == "quarantine")
    assert quarantine["worker"] == 0

    # doctor ranks the quarantine as the top (critical) finding
    from sheeprl_tpu.diag.findings import run_detectors
    from sheeprl_tpu.diag.timeline import Timeline, iter_events

    tl = Timeline(list(iter_events(base / "version_0" / "telemetry.jsonl")))
    findings = run_detectors(tl)
    assert findings and findings[0].code == "quarantine"
    assert findings[0].severity == "critical"


def test_degraded_round_stages_the_next_burst_by_its_own_env_steps(monkeypatch):
    """After worker 0 is quarantined a round carries 1 env step, not
    `num_envs` = 2. The learner's loop has no fleet branch: it asks the packet
    how many steps came, so the burst it stages for the next iteration is the
    one that iteration takes (peeking `policy_step + num_envs` would stage
    bursts of 2 that every `take(1)` throws away for a synchronous sample)."""
    from sheeprl_tpu.algos.sac import sac
    from sheeprl_tpu.cli import run

    calls = []

    def recording(make):
        def make_uniform_prefetcher(*args, **kwargs):
            prefetch = make(*args, **kwargs)
            stage, take = prefetch.stage, prefetch.take
            prefetch.stage = lambda g: (calls.append(("stage", g)), stage(g))[1]
            prefetch.take = lambda g: (calls.append(("take", g)), take(g))[1]
            return prefetch

        return make_uniform_prefetcher

    monkeypatch.setattr(sac, "make_uniform_prefetcher", recording(sac.make_uniform_prefetcher))
    run(
        _sac_args(
            "fleet_degraded_stage",
            total=64,
            extra=[
                "algo.fleet.workers=2",
                "fleet.max_fails=1",
                "resilience.chaos.enabled=True",
                "resilience.chaos.crash_at_step=10",
                "resilience.chaos.crash_workers=[0]",
                "resilience.chaos.crash_repeat=True",
            ],
        )
    )
    st, base = _final_ckpt("fleet_degraded_stage")
    assert st["policy_step"] == 64
    _, fleet_evs = _fleet_events(base)
    assert "quarantine" in [e["action"] for e in fleet_evs]

    takes = [i for i, (kind, _) in enumerate(calls) if kind == "take"]
    degraded = [i for i in takes if calls[i][1] == 1]
    assert len(degraded) >= 8, calls  # rounds of one env step did train
    # every burst after the first degraded one was staged as taken: the stage
    # call right before it (the previous iteration's last) names the same g
    for i in degraded[1:]:
        assert calls[i - 1] == ("stage", 1), calls[max(0, i - 3) : i + 1]
    # and full-strength rounds before the quarantine were staged as 2
    assert ("stage", 2) in calls and ("take", 2) in calls


# ---------------------------------------------------------------------------
# e2e: torn packet → CRC rejection → worker fault path
# ---------------------------------------------------------------------------
def test_torn_packet_is_detected_and_worker_respawned():
    from sheeprl_tpu.cli import run

    run(
        _sac_args(
            "fleet_torn",
            total=64,
            extra=[
                "algo.fleet.workers=2",
                "resilience.chaos.enabled=True",
                "resilience.chaos.torn_packet_at=5",
                "resilience.chaos.torn_workers=[0]",
            ],
        )
    )
    st, base = _final_ckpt("fleet_torn")
    assert st["policy_step"] == 64  # the torn packet was discarded, not applied
    events, fleet_evs = _fleet_events(base)
    actions = [e["action"] for e in fleet_evs]
    assert "torn_packet" in actions and "respawn" in actions
    intervals = [e for e in fleet_evs if e["action"] == "interval"]
    assert intervals[-1]["torn_packets"] >= 1


# ---------------------------------------------------------------------------
# e2e: SIGTERM drain with live workers → resumable checkpoint
# ---------------------------------------------------------------------------
def test_sigterm_drain_with_live_workers_leaves_consistent_checkpoint():
    from sheeprl_tpu.cli import run

    run(
        _sac_args(
            "fleet_drain",
            total=4096,
            extra=[
                "algo.fleet.workers=2",
                "resilience.preemption.poll_every_s=0.0",
                "resilience.preemption.poller._target_=sheeprl_tpu.resilience.preemption.CountdownPoller",
                "resilience.preemption.poller.n=20",
            ],
        )
    )
    st, base = _final_ckpt("fleet_drain")
    assert 0 < st["policy_step"] < 4096
    # consistent buffer: one full-width row per round of 2 env steps — the
    # step counter exactly matches the content (incomplete trailing rounds
    # are DROPPED at drain, never half-applied)
    assert st["rb"]["pos"] * 2 == st["policy_step"]

    events, fleet_evs = _fleet_events(base)
    assert [e["action"] for e in events if e["event"] == "preempt"] == [
        "requested",
        "checkpointed",
    ]
    assert any(e["action"] == "drain" for e in fleet_evs)
    # every worker process is gone and the preemption flag was consumed
    from sheeprl_tpu.resilience.preemption import preemption_requested

    assert not preemption_requested()
    assert not [t for t in threading.enumerate() if t.name.startswith("fleet-")]


# ---------------------------------------------------------------------------
# the full external-SIGKILL smoke script (subprocess, slow): a REAL worker
# process murdered by the OS mid-run, not a chaos-scripted exit
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("transport", ["mp", "socket"])
def test_fleet_smoke_script_survives_external_sigkill(tmp_path, transport):
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(repo, "scripts", "fleet_smoke.py"),
            f"transport={transport}",
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        text=True,
        timeout=1500,
        cwd=tmp_path,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": repo},
    )
    assert proc.stdout.strip(), f"smoke printed nothing (rc={proc.returncode})"
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 0 and rec["ok"], rec
    assert rec["transport"] == transport
    assert rec["final_step"] == 1024  # no env steps lost to the kill
    assert rec["incident_found"], rec  # doctor surfaced the incident
    if transport == "socket":
        # the respawned incarnation re-attached over TCP
        assert rec["net_accepts"] >= 3, rec
