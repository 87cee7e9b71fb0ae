"""What only the cells of the `ppo_recurrent_sequence` adapter keep (a sequence policy
on the recurrent on-policy loop): the share of each layer, the traffic the
issue wrote, the cut's table checked by `jax.eval_shape`, the counts of work
by hand, and that the readers it brought read nothing where there is nothing
to read. What holds for any cell is in `pb_checks.py`; the control, the fault
and a run through the harness are in `test_pb_ppo_recurrent_sequence_control.py`."""
import json
import os

import numpy as np
import pytest

from pb_checks import cells_of, spec_and_adapter
from pb_helpers import BENCH_FILE, ROOT, bench
from perfbench.adapters import ppo_recurrent_sequence as adapter
from perfbench.envs import REGISTRY, reset_registry
from perfbench.token_envs import TokenEpisodesEnv

CELLS = cells_of("ppo_recurrent_sequence")
OWN_READERS = ["train_step.mla_ms", "train_step.moe_ms", "train_step.mhc_ms", "train_step.dense_mlp_ms", "train_step.head_ms",
               "train_step.moe_roofline", "player.decode_device_ms", "player.decode_roofline", "moe.slot_occupancy_pct"]
TINY_MIX = "tests/perfbench/fixtures/traffic/gen4x32.json"


def test_the_benchmark_has_a_cell_of_this_adapter():
    assert CELLS and all(spec_and_adapter(c)[1] is adapter for c in CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_the_traffic_and_the_share_its_files_state(cell):
    from perfbench.run import load_cell, overrides_for
    from sheeprl_tpu.config import compose

    spec = load_cell(cell, BENCH_FILE)
    conf, mix = spec["config"], spec["mix"]
    cfg = compose("config", overrides_for(spec, 3000000019, False))
    a, b = cfg.algo, cfg.algo.backbone
    assert str(a.name) == "ppo_recurrent" and str(b.name) == "xing4" and str(cfg.fabric.precision) == "32-true"
    assert (int(cfg.env.num_envs), int(a.rollout_steps), int(a.per_rank_sequence_length)) == (mix["num_envs"], mix["rollout_steps"], mix["rollout_steps"])
    assert (int(a.update_epochs), int(a.per_rank_num_batches)) == (mix["update_epochs"], mix["minibatches"])
    assert list(a.mlp_keys.encoder) == list(mix["observation"]) == ["token"] and mix["observation"]["token"] == {"shape": [1], "dtype": "int32"}
    assert mix["action"] == {"type": "discrete", "n": int(b.vocab_held)} and mix["generator"] == "perfbench.token_envs.TokenEpisodesEnv"
    assert mix["warmup_train_calls"] == adapter.CHECK_CALLS + 1 + 2
    # the share is what the file's reduced keys say, and no width moved: the router stays as wide as published
    assert (int(b.experts_held), int(b.heads_held), int(b.vocab_held), int(b.num_hidden_layers), int(b.first_k_dense_replace)) == (
        conf["n_routed_experts"], conf["num_attention_heads"], conf["vocab_size"], conf["num_hidden_layers"], conf["first_k_dense_replace"])
    assert int(b.n_routed_experts) == conf["widths"]["router_width"] and 0 <= int(b.first_expert) <= int(b.n_routed_experts) - int(b.experts_held)
    assert all(conf[k] == conf["widths"][k] for k in adapter.WIDTHS)
    assert "deployment" in conf and "8 chips" in conf["deployment"] and conf["assumed"]
    # one iteration holds twice in a traced window at the rates the builder read (PERF.md section 5)
    assert mix["trace_seconds"] >= 10


def test_the_accepted_cell_is_the_issues(benchmark_json):
    cell = "xing4_a4b.gen32x512"
    assert cell in CELLS
    entry = next(w for w in benchmark_json["workloads"] if w["name"] == cell)
    assert entry["chips"] == 1 and (entry["config"], entry["traffic"]) == ("xing4_a4b", "gen32x512")
    gap = next(m for m in benchmark_json["end_to_end"] if m["name"] == "step_gap_p95_ms")
    assert cell not in gap["workloads"]  # its long gap is a host-bound decode step
    per_layer = {m["name"]: m for m in benchmark_json["per_layer"]}
    assert all(per_layer[name]["workloads"] == [cell] for name in OWN_READERS)
    listed = sorted(name for name, m in per_layer.items() if cell in m["workloads"])
    assert listed == sorted(OWN_READERS + ["train_step.device_ms", "train_step.mfu", "train_step.optimizer_ms", "train_step.unscoped_pct",
                                           "device.idle_pct", "device.peak_hbm_gib", "device.idle_unattributed_pct", "loop.train_span_pct",
                                           "env.self_ms"])
    spec, _ = spec_and_adapter(cell)
    mix = spec["mix"]
    assert (mix["num_envs"], mix["rollout_steps"], mix["episode_unit"], mix["update_epochs"], mix["minibatches"]) == (32, 512, 64, 2, 8)
    assert mix["action"]["n"] == 16384 and mix["own_token_share"] == 0.1


def test_the_cut_is_the_issues_table_by_eval_shape():
    spec, _ = spec_and_adapter("xing4_a4b.gen32x512")
    _, shapes = adapter.program_shapes(spec)
    count = lambda keep: sum(int(np.prod(s)) for n, (s, _) in shapes.items() if keep(n))  # noqa: E731
    M = 1e6
    assert count(lambda n: "/attn/" in n) / 5 / M == pytest.approx(7.77, abs=0.01)           # MLA a layer, 4 heads held
    assert count(lambda n: n.startswith("layer_0/mlp/")) / M == pytest.approx(99.09, abs=0.01)  # the dense layer's MLP
    assert count(lambda n: n.startswith("layer_1/") and "_hc/" in n) / M == pytest.approx(0.69, abs=0.01)
    assert count(lambda n: n.startswith("layer_1/moe/experts/")) / 8 / M == pytest.approx(11.01, abs=0.01)
    assert count(lambda n: n.startswith("layer_1/")) / M == pytest.approx(107.8, abs=0.1)
    assert count(lambda n: n.startswith(("embed/", "head/"))) / M == pytest.approx(117.4, abs=0.1)
    assert count(lambda n: True) / M == pytest.approx(656.1, abs=0.1) and shapes["value/kernel"][0] == (3584, 1)
    kept = adapter.kept_bytes(shapes, spec)
    assert kept["cache"] == 32 * 512 * 576 * 4 * 5 and kept["total"] / 2**30 == pytest.approx(7.51, abs=0.01)


def test_work_is_counted_from_shapes_and_the_routings_expected_load():
    """By hand at the tests' small size: 2 of 8 experts held with top-2, so half a pair a token comes here."""
    spec, _ = spec_and_adapter("xing4_tiny.gen4x32", "tests/perfbench/fixtures/seq_bench.json")
    _, shapes = adapter.program_shapes(spec)
    macs = adapter.token_macs(shapes, spec)
    C, n = 64, 4
    attn = C * 24 + 24 * 2 * 16 + C * 24 + 16 * 2 * 16 + 2 * 8 * C
    assert macs["mla"] == 3 * attn and macs["mhc"] == 3 * 2 * n * C * (n + n + n * n)
    assert macs["dense_mlp"] == 3 * C * 96 and macs["moe"] == 2 * (C * 8 + 3 * C * 32) and macs["experts"] == 2 * 0.5 * 3 * C * 32
    assert macs["head"] == C * 32 + C
    # a token sees (8 + 1) / 2 of its own unit and, behind it, 8 x (1/2 + 1/4 + ...) of the uncut units: mean over the 4 units
    ctx = 4.5 + 8 * np.mean([0, 0.5, 0.75, 0.875])
    assert adapter.mean_context(spec["mix"]) == pytest.approx(ctx)
    assert macs["attend_train"] == 3 * 2 * (16 + 8) * ctx and macs["attend_decode"] == 3 * 2 * (24 + 16) * ctx
    kernels = sum(v for k, v in macs.items() if not k.startswith("attend_"))
    assert adapter.step_flops(shapes, spec) == {"total": 6.0 * (kernels + macs["attend_train"]) * 64, "per_env_step": 2.0 * (kernels + macs["attend_decode"])}
    assert adapter.expert_flops(shapes, spec, 10.0) == 18.0 * C * 32 * 10 and adapter.expert_bytes(shapes) == 2 * 2 * 3 * C * 32 * 4
    # 4 envs with top-2 of 8: a held expert is read in 1 - (3/4)^4 of the steps; everything else but the embedding always
    params = sum(int(np.prod(s)) * 4 for n_, (s, _) in shapes.items() if not n_.startswith("embed/") and "/moe/experts/" not in n_)
    experts = adapter.expert_bytes(shapes) * (1 - 0.75 ** 4)
    assert adapter.decode_bytes(shapes, spec) == pytest.approx(params + experts + 4 * C * 4 + 4 * 3 * ctx * 24 * 4, rel=1e-12)
    spec, _ = spec_and_adapter("xing4_a4b.gen32x512")
    _, shapes = adapter.program_shapes(spec)
    assert adapter.decode_bytes(shapes, spec) / 1e9 == pytest.approx(2.26, abs=0.01)  # 2.44 with every held expert read every step


def test_every_rollout_is_tiled_by_whole_episodes_of_the_stated_lengths():
    reset_registry()
    lengths, firsts = [], 0
    for rank in range(4):
        env = TokenEpisodesEnv("gen32x512", bench_seed=3000000019 + rank, rank=rank)
        for rollout in range(6):
            ends = env.episode_ends(rollout)
            assert ends[-1] == 512 and all(e % 64 == 0 for e in ends) and ends == sorted(set(ends))
            lengths += list(np.diff([0] + ends))
    assert set(lengths) <= {64, 128, 192, 256, 320, 384, 448, 512} and 2.0 < 24 * 512 / sum(1 for _ in lengths) / 64 < 4.5
    env = TokenEpisodesEnv(TINY_MIX, bench_seed=7, rank=2)
    assert REGISTRY[2] is env
    obs, _ = env.reset()
    steps_in_episode, seen_tokens = 0, [int(obs["token"][0])]
    for t in range(96):  # three rollouts of 32
        action = (5 * t + 3) % 32
        obs, reward, term, trunc, _ = env.step(action)
        steps_in_episode += 1
        seen_tokens.append(int(obs["token"][0]))
        assert not trunc and int(obs["token"][0]) in (action, int(env._own[(env.n - 1) % len(env._own)]))
        if term:
            firsts += 1
            assert steps_in_episode in (8, 16, 24, 32) and reward == env.pay(seen_tokens) and (t + 1) % 8 == 0
            obs, _ = env.reset()
            steps_in_episode, seen_tokens = 0, [int(obs["token"][0])]
        else:
            assert reward == 0.0
        if (t + 1) % 32 == 0:
            assert term  # no episode crosses a rollout boundary
    assert firsts >= 3 and len(env.seen()) == 97 and env.n == 97 + firsts
    assert [env.log_first[n] for n in env.seen()].count(True) == firsts + 1


def test_a_rollout_from_the_generator_is_what_the_row_check_accepts_and_a_wrong_row_is_seen():
    spec, _ = spec_and_adapter("xing4_tiny.gen4x32", "tests/perfbench/fixtures/seq_bench.json")
    cfg, _ = adapter.program_shapes(spec)
    sz = adapter.sizes_for(cfg, 2, 2)
    reset_registry()
    rollout = adapter.rollout_from_generator(spec, 41, sz)
    assert adapter.rollout_rows(rollout, dict(REGISTRY), sz) == (4 * 32, 0)
    assert rollout["dones"][:, -1].all() and rollout["is_first"][:, 0].all() and rollout["dones"].sum() == rollout["is_first"].sum()
    rollout["actions"][1, 7] += 1
    rollout["rewards"][3, 31] += 0.5
    assert adapter.rollout_rows(rollout, dict(REGISTRY), sz) == (4 * 32, 2)


@pytest.mark.parametrize("metric", OWN_READERS)
def test_a_reader_of_this_family_reads_nothing_where_there_is_nothing_to_read(metric):
    """On the parent, in another adapter's cell, or without a capture: no number and no exception."""
    from types import SimpleNamespace

    from perfbench import peaks
    from perfbench.run import metric_reader

    ctx = {"capture": None, "trace": {"programs": {}, "window_s": 0.0}, "trace_dir": None, "window": {"grad_steps": 0, "train_calls": 0, "env_steps": 0, "seconds": 1.0},
           "rehearse": False, "shapes": {}, "spec": {}, "adapter": SimpleNamespace(), "peaks": peaks, "device_kind": "TPU v5 lite", "envs": {}}
    assert metric_reader(metric)(ctx) is None


def test_the_event_readers_sum_the_runs_moe_load_events(tmp_path):
    from perfbench import program_events
    from perfbench.run import metric_reader

    run_dir = tmp_path / "logs" / "runs" / "algo" / "env" / "run" / "version_0"
    run_dir.mkdir(parents=True)
    loads = [{"event": "moe_load", "routed_here": 100, "rows": 800, "slot_occupancy": 0.125, "max_over_mean": 1.5, "dropped": 0},
             {"event": "moe_load", "routed_here": 140, "rows": 800, "slot_occupancy": 0.175, "max_over_mean": 1.2, "dropped": 0}]
    (run_dir / "telemetry.jsonl").write_text("\n".join(json.dumps(e) for e in [{"event": "startup"}] + loads) + "\n")
    ctx = {"trace_dir": str(tmp_path / "trace")}
    assert program_events.events(ctx, "moe_load") == loads and program_events.events(ctx, "no_such") == []
    assert metric_reader("moe.slot_occupancy_pct")(ctx) == pytest.approx(100.0 * 240 / 1600)


def test_the_limits_file_says_what_it_was_set_from():
    spec, _ = spec_and_adapter("xing4_a4b.gen32x512")
    with open(spec["limits_file"]) as f:
        limits = json.load(f)
    assert set(limits["limits"]) == {"routing_flips", "values_gap", "logprobs_gap", "values_worst", "logprobs_worst", "advantages_gap",
                                     "loss_gap_policy", "loss_gap_value", "loss_gap_entropy", "update_gap", "update_mid"}
    assert all(f"{kind} " in limits["set_from"] for kind in adapter.fault_kinds)  # each planted fault's reading is given
    assert "control" in limits["set_from"] and "my chip runs, PR 38" in limits["set_from"]
    assert os.path.isfile(os.path.join(ROOT, "perfbench", "references", "ppo_recurrent_sequence.py")) and len(bench()["workloads"]) >= 3
