"""Overlap engine (sheeprl_tpu/engine/overlap.py) — the invariants:

* the SPSC ring is FIFO, bounded, and safe across a producer/consumer pair;
* the staleness gate really blocks the player once more than
  `staleness_bound` bursts are unpublished;
* the inline source (`enabled=False`) runs `play` on the caller's thread,
  one packet a `take()`, with no thread and no `overlap` event;
* replay-ratio accounting is EXACT: SAC, DreamerV3 and PPO runs end with the
  same step counters, gradient steps, Ratio state and buffer position with
  the player thread as with the inline source;
* a 512-step DreamerV3 run emits `overlap` telemetry (player-stall fraction
  reported), player env-interaction spans land in the same log intervals as
  learner train spans, observed staleness stays within the bound, and the
  player's pinned act never retraces;
* RunGuard SIGTERM drain works with the player thread live: one final
  checkpoint, clean preempt lifecycle, no lingering player thread.
"""
import json
import signal
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from sheeprl_tpu.engine import OverlapEngine, Packet, RecordingSink, SpscRing


# ---------------------------------------------------------------------------
# unit: the queue
# ---------------------------------------------------------------------------
def test_spsc_ring_fifo_and_bounded():
    r = SpscRing(3)
    assert r.capacity == 3
    assert r.try_get() is r  # empty sentinel
    assert all(r.try_put(i) for i in range(3))
    assert not r.try_put(99)  # full
    assert len(r) == 3
    assert [r.try_get() for _ in range(3)] == [0, 1, 2]
    assert r.try_get() is r


def test_spsc_ring_cross_thread_ordering():
    r = SpscRing(8)
    n = 20_000
    got = []

    def produce():
        for i in range(n):
            while not r.try_put(i):
                time.sleep(0)

    t = threading.Thread(target=produce)
    t.start()
    while len(got) < n:
        item = r.try_get()
        if item is not r:
            got.append(item)
    t.join()
    assert got == list(range(n))  # FIFO, nothing lost or duplicated


# ---------------------------------------------------------------------------
# unit: packets / recorded buffer ops
# ---------------------------------------------------------------------------
class _FakeRB:
    def __init__(self):
        self.calls = []

    def add(self, data, idxes=None, validate_args=False):
        self.calls.append(("add", {k: v.copy() for k, v in data.items()}, idxes))

    def mark_restart(self, i):
        self.calls.append(("restart", i, None))


def test_recording_sink_preserves_order_and_snapshots_arrays():
    sink = RecordingSink()
    row = {"x": np.zeros((1, 2, 1), np.float32)}
    sink.add(row, validate_args=True)
    sink.mark_restart(1)
    sink.add({"x": np.ones((1, 1, 1), np.float32)}, [1])
    row["x"][:] = 7.0  # mutate AFTER recording: the snapshot must not move

    rb = _FakeRB()
    Packet(sink, 2).apply(rb)
    assert [c[0] for c in rb.calls] == ["add", "restart", "add"]
    assert rb.calls[0][1]["x"].sum() == 0.0  # copied at record time
    assert rb.calls[2][2] == [1]


# ---------------------------------------------------------------------------
# unit: the staleness gate
# ---------------------------------------------------------------------------
def test_staleness_gate_blocks_player_until_publish():
    eng = OverlapEngine(enabled=True, queue_depth=8, staleness_bound=1, total_steps=10_000)
    # simulate a pipelined learner: two bursts started, none published
    eng.burst_started()
    eng.burst_started()
    eng.start(lambda: Packet(None, 1))
    time.sleep(0.25)
    assert eng.packets_produced == 0  # 2 unpublished bursts > bound of 1
    eng.published()
    deadline = time.time() + 5
    while eng.packets_produced == 0 and time.time() < deadline:
        time.sleep(0.01)
    assert eng.packets_produced > 0  # gate released
    eng.shutdown()


def test_backpressure_applies_before_acting_not_after():
    """The player must WAIT for a free queue slot before collecting a
    slice — blocking after collection would let it act one slice beyond
    the bound with params one publish older than intended (the PPO
    rollout-pipeline case)."""
    calls = []
    eng = OverlapEngine(enabled=True, queue_depth=1, total_steps=100)
    eng.start(lambda: (calls.append(eng._pub_seq), Packet(None, 1))[1])
    deadline = time.time() + 5
    while not calls and time.time() < deadline:
        time.sleep(0.01)
    time.sleep(0.25)
    assert len(calls) == 1  # slot taken by slice 1 → slice 2 NOT collected yet
    assert len(eng.take(max_packets=1)) == 1  # learner frees the slot
    deadline = time.time() + 5
    while len(calls) < 2 and time.time() < deadline:
        time.sleep(0.01)
    time.sleep(0.25)
    assert len(calls) == 2  # exactly one more slice, no run-ahead
    eng.shutdown()


def test_engine_take_drains_fifo_and_shutdown_drains_rest():
    eng = OverlapEngine(enabled=True, queue_depth=4, total_steps=40)
    eng.start(lambda: Packet(None, 2))
    pkts = eng.take()
    assert pkts and all(p.env_steps == 2 for p in pkts)
    # stop while the player still has queued packets; shutdown must hand
    # them to the absorb callback, not drop them
    drained = []
    leftover = eng.shutdown(lambda p: drained.append(p))
    assert leftover == sum(p.env_steps for p in drained)
    assert eng.acked_steps == eng.produced_steps  # every step accounted


# ---------------------------------------------------------------------------
# unit: the inline source
# ---------------------------------------------------------------------------
def test_inline_source_plays_on_the_callers_thread_one_packet_a_take():
    events, played_on = [], []

    class Telem:
        def emit(self, rec):
            events.append(rec)

    def play():
        played_on.append(threading.current_thread())
        assert not [t for t in threading.enumerate() if t.name == "overlap-player"]
        return Packet(None, 3)

    eng = OverlapEngine(enabled=False, queue_depth=4, total_steps=100, initial_step=6, telem=Telem(), stats_every_s=0.0)
    assert eng.run_ahead == 0  # nothing is produced beyond the packet the learner holds
    assert eng.start(play) is eng and eng._thread is None and played_on == []  # start only keeps `play`
    for n in (1, 2):
        pkts = eng.take(max_packets=1)
        assert [p.env_steps for p in pkts] == [3] and len(played_on) == n  # ONE play a take
        assert eng.burst == n and pkts[0].version == n - 1  # the claim counter of the threaded source
        assert eng.published() is None
    assert played_on == [threading.current_thread()] * 2
    assert eng.acked_steps == 12 and eng.queue_len == 0
    drained = []
    assert eng.shutdown(drained.append) == 0 and drained == []  # nothing is ever queued
    assert events == []  # no `overlap` event, no trace record

    def broken():
        raise ValueError("env died")

    with pytest.raises(ValueError, match="env died"):  # on the caller's own stack, unwrapped
        OverlapEngine(enabled=False).start(broken).take()
    assert OverlapEngine(enabled=False).start(lambda: None).take() == []  # `play` may end the run


# ---------------------------------------------------------------------------
# e2e: exact replay-ratio ledger (player thread vs inline), three algorithms
# ---------------------------------------------------------------------------
def _sac_args(run_name, overlap, total=512):
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
        f"algo.overlap.enabled={overlap}",
        "buffer.size=512",
        "buffer.memmap=False",
        "buffer.checkpoint=True",
        "checkpoint.every=0",
        "checkpoint.save_last=True",
        "model_manager.disabled=True",
        "seed=3",
        f"run_name={run_name}",
    ]


def _dv3_args(run_name, total=512, extra=()):
    return [
        "exp=dreamer_v3",
        "env=dummy",
        "env.id=discrete_dummy",
        "env.num_envs=2",
        "env.sync_env=True",
        "env.capture_video=False",
        "algo=dreamer_v3_XS",
        f"algo.total_steps={total}",
        "algo.learning_starts=64",
        "algo.replay_ratio=0.25",
        "algo.per_rank_batch_size=2",
        "algo.per_rank_sequence_length=2",
        "algo.horizon=4",
        "algo.dense_units=16",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=16",
        "algo.world_model.transition_model.hidden_size=16",
        "algo.world_model.representation_model.hidden_size=16",
        "algo.world_model.discrete_size=4",
        "algo.world_model.stochastic_size=4",
        "algo.cnn_keys.encoder=[rgb]",
        "algo.mlp_keys.encoder=[]",
        "algo.run_test=False",
        "buffer.size=512",
        "buffer.memmap=False",
        "metric.log_level=1",
        "model_manager.disabled=True",
        f"run_name={run_name}",
    ] + list(extra)


def _ppo_args(run_name, total=256):
    return [
        "exp=ppo",
        "env=dummy",
        "env.id=discrete_dummy",
        "env.num_envs=2",
        "env.sync_env=True",
        "env.capture_video=False",
        "buffer.memmap=False",
        "metric.log_level=1",
        f"algo.total_steps={total}",
        "algo.rollout_steps=16",
        "algo.update_epochs=1",
        "algo.per_rank_batch_size=8",
        "algo.encoder.cnn_features_dim=16",
        "algo.encoder.mlp_features_dim=16",
        "algo.encoder.dense_units=8",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.cnn_keys.encoder=[rgb]",
        "algo.mlp_keys.encoder=[state]",
        "algo.run_test=False",
        "checkpoint.every=0",
        "checkpoint.save_last=True",
        "model_manager.disabled=True",
        f"run_name={run_name}",
    ]


def _final_ckpt(run_name, algo="sac", env_id="continuous_dummy"):
    from sheeprl_tpu.utils.checkpoint import CheckpointManager

    base = Path("logs/runs") / algo / env_id / run_name
    cks = sorted(
        (base / "version_0" / "checkpoint").glob("ckpt_*.ckpt"),
        key=lambda p: int(p.stem.split("_")[1]),
    )
    assert cks, f"no checkpoint under {base}"
    return CheckpointManager.load(cks[-1]), base


def _optimizer_steps(state):
    """The gradient steps a checkpoint's optimizers have taken: every optax
    `count` leaf of the state, so a run that trained more or less shows."""
    import jax

    opt = state.get("opt_states", state.get("opt_state"))
    counts = [
        int(np.asarray(leaf))
        for path, leaf in jax.tree_util.tree_leaves_with_path(opt)
        if "count" in jax.tree_util.keystr(path)
    ]
    assert counts, "no optax count in the checkpoint's optimizer state"
    return counts


def _buffer_positions(rb_state):
    buffers = rb_state["buffers"] if "buffers" in rb_state else [rb_state]
    return [(b["pos"], b["full"]) for b in buffers]


_LEDGER_RUNS = {
    # algo: (args of one run, env id, final policy_step)
    "sac": (lambda name, overlap: _sac_args(name, overlap), "continuous_dummy", 512),
    "dreamer_v3": (
        lambda name, overlap: _dv3_args(
            name,
            total=256,
            extra=[f"algo.overlap.enabled={overlap}", "buffer.checkpoint=True", "checkpoint.every=0", "checkpoint.save_last=True"],
        ),
        "discrete_dummy",
        256,
    ),
    "ppo": (lambda name, overlap: _ppo_args(name) + [f"algo.overlap.enabled={overlap}"], "discrete_dummy", 256),
}


@pytest.mark.parametrize("algo", sorted(_LEDGER_RUNS))
def test_overlap_replay_ratio_ledger_matches_inline(algo):
    """The env-step:grad-step budget must be IDENTICAL between the two
    in-process sources: same final step, same gradient steps, same Ratio
    controller state, same buffer fill — the player thread only changes
    *when* work runs. The inline run (`algo.overlap.enabled=False`: `play` on
    the learner's thread) starts no player thread and emits no `overlap`
    event."""
    from sheeprl_tpu.cli import run

    args, env_id, final_step = _LEDGER_RUNS[algo]
    run(args(f"ledger_{algo}_on", True))
    on, base_on = _final_ckpt(f"ledger_{algo}_on", algo, env_id)
    run(args(f"ledger_{algo}_off", False))
    off, base_off = _final_ckpt(f"ledger_{algo}_off", algo, env_id)

    assert on["policy_step"] == off["policy_step"] == final_step
    assert _optimizer_steps(on) == _optimizer_steps(off) and min(_optimizer_steps(on)) > 0
    for key in ("cumulative_grad_steps", "ratio", "update"):  # what each algorithm keeps of its ledger
        assert (key in on) == (key in off)
        if key in on:
            assert on[key] == off[key], key
    assert ("rb" in on) == ("rb" in off) == (algo != "ppo")  # PPO keeps no buffer across updates
    if "rb" in on:
        assert _buffer_positions(on["rb"]) == _buffer_positions(off["rb"])

    # the threaded run's telemetry carries the engine's interval events, the
    # inline run's none: no thread, no ring, no gate to report on
    def overlap_events(base):
        return [e for e in map(json.loads, open(base / "version_0" / "telemetry.jsonl")) if e["event"] == "overlap"]

    threaded = overlap_events(base_on)
    assert threaded, "no overlap events in the JSONL stream"
    assert all(e["staleness_max"] <= 1 for e in threaded)  # bounded staleness
    assert all("player_stall_frac" in e for e in threaded)
    assert overlap_events(base_off) == []


# ---------------------------------------------------------------------------
# e2e: 512-step DreamerV3 — overlap telemetry, span overlap, retrace==0
# ---------------------------------------------------------------------------
def test_dreamer_v3_overlap_512_steps_telemetry_and_no_retraces():
    from sheeprl_tpu.cli import run
    from sheeprl_tpu.telemetry.schema import validate_jsonl

    run(
        _dv3_args(
            "overlap_dv3",
            extra=["algo.overlap.stats_every_s=0.5", "metric.log_every=128", "checkpoint.save_last=False"],
        )
    )
    stream = Path("logs/runs/dreamer_v3/discrete_dummy/overlap_dv3/version_0/telemetry.jsonl")
    assert validate_jsonl(stream) == []
    events = [json.loads(ln) for ln in open(stream)]

    # overlap events present, player-stall fraction reported, staleness ≤ 1
    overlap_events = [e for e in events if e["event"] == "overlap"]
    assert overlap_events
    assert all("player_stall_frac" in e for e in overlap_events)
    assert all(e["staleness_max"] <= 1 for e in overlap_events)
    assert overlap_events[-1]["bursts"] > 0

    # player env-stepping spans land in the same intervals as learner
    # train-burst spans — the two phases really ran concurrently
    logs = [e for e in events if e["event"] == "log" and e["step"] > 64]
    both = [
        e
        for e in logs
        if e["spans"].get("Time/env_interaction_time", 0) > 0
        and e["spans"].get("Time/train_time", 0) > 0
    ]
    assert both, f"no interval shows env+train spans together: {[e['spans'] for e in logs]}"

    # the player's pinned act never retraced (retrace-detector accounting
    # wraps the jitted player step; the shutdown record carries the delta)
    shutdown = [e for e in events if e["event"] == "shutdown"]
    assert shutdown and shutdown[-1]["xla"].get("retraces", 0) == 0


# ---------------------------------------------------------------------------
# e2e: RunGuard SIGTERM drain with the player thread live
# ---------------------------------------------------------------------------
def test_sigterm_drain_with_live_player_thread():
    """Preemption mid-run: player stops feeding, learner finishes its burst,
    the final checkpoint is consistent (counter == buffer content), and the
    player thread is gone afterwards."""
    from sheeprl_tpu.cli import run

    args = _sac_args("overlap_drain", True, total=4096) + [
        "resilience.preemption.poll_every_s=0.0",
        "resilience.preemption.poller._target_=sheeprl_tpu.resilience.preemption.CountdownPoller",
        "resilience.preemption.poller.n=6",
    ]
    run(args)
    st, base = _final_ckpt("overlap_drain")
    assert 0 < st["policy_step"] < 4096
    # consistent buffer: the drained transitions landed before the save
    # (2 envs → one buffer row per 2 policy steps; no wrap this early)
    assert st["rb"]["pos"] * 2 == st["policy_step"]

    events = [json.loads(ln) for ln in open(base / "version_0" / "telemetry.jsonl")]
    actions = [e["action"] for e in events if e["event"] == "preempt"]
    assert actions == ["requested", "checkpointed"]
    assert not [t for t in threading.enumerate() if t.name == "overlap-player"]
    # the guard observed + drained the request and cleared the process-wide
    # flag, so the next in-process run starts clean
    from sheeprl_tpu.resilience.preemption import preemption_requested

    assert not preemption_requested()
