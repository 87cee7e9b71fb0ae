"""What only the cells of the `ppo_recurrent_diffusion` adapter keep (a
block-diffusion policy on the recurrent on-policy loop): the share of each
layer, the cell's traffic, the cut's table checked by
`jax.eval_shape`, the counts of work by hand, the generator of blocks, and
that the readers it brought read nothing where there is nothing to read. What
holds for any cell is in `pb_checks.py`; the control, the faults and a run
through the harness are in `test_pb_ppo_recurrent_diffusion_control*.py`."""
import json
import re

import numpy as np
import pytest

from pb_checks import cells_of, spec_and_adapter
from pb_helpers import BENCH_FILE
from perfbench.adapters import ppo_recurrent_diffusion as adapter
from perfbench.block_envs import BlockEpisodesEnv
from perfbench.envs import REGISTRY, reset_registry

CELLS = cells_of("ppo_recurrent_diffusion")
CELL, TINY_BENCH, TINY_CELL = "sdar_a3b.blk32x128", "tests/perfbench/fixtures/bd_bench.json", "sdar_tiny.blk4x8"
OWN_READERS = ["train_step.bd_attn_ms", "train_step.bd_attn_roofline", "player.block_decode_ms", "player.block_decode_roofline"]


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
    assert str(a.name) == "ppo_recurrent" and str(b.name) == "sdar" and str(cfg.fabric.precision) == "32-true"
    assert (int(cfg.env.num_envs), int(a.rollout_steps), int(a.per_rank_sequence_length)) == (mix["num_envs"], mix["rollout_steps"], mix["rollout_steps"])
    assert (int(a.update_epochs), int(a.per_rank_num_batches)) == (mix["update_epochs"], mix["minibatches"])
    assert list(a.mlp_keys.encoder) == list(mix["observation"]) == ["block"] and mix["observation"]["block"] == {"shape": [int(b.block_length)], "dtype": "int32"}
    assert mix["action"] == {"type": "multi_discrete", "n": int(b.vocab_held), "size": int(b.block_length)} and mix["mask_id"] == int(b.vocab_held) - 1
    assert mix["generator"] == "perfbench.block_envs.BlockEpisodesEnv" and mix["warmup_train_calls"] == adapter.CHECK_CALLS + 1 + 2
    # the share is what the file's reduced keys say, and no width moved: the router stays as wide as published
    assert (int(b.experts_held), int(b.vocab_held), int(b.num_hidden_layers)) == (conf["num_experts"], conf["vocab_size"], conf["num_hidden_layers"])
    assert int(b.n_routed_experts) == conf["widths"]["router_width"] == 128 and int(b.first_expert) == 0
    assert int(b.heads_held) == int(b.num_attention_heads) == conf["num_attention_heads"]  # attention is data parallel: every head held
    assert all(conf[k] == conf["widths"][k] for k in adapter.WIDTHS if k in conf) and conf["rope_scaling"] is None
    assert (str(b.attention), str(b.scoring_func), float(b.routed_scaling_factor)) == ("gqa", "softmax", 1.0) and conf["norm_topk_prob"]
    assert "deployment" in conf and "8 chips" in conf["deployment"] and conf["assumed"] and mix["trace_seconds"] >= 10


def test_the_accepted_cell_has_its_traffic_and_its_readers(benchmark_json):
    assert CELL in CELLS
    entry = next(w for w in benchmark_json["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and (entry["config"], entry["traffic"]) == ("sdar_a3b", "blk32x128") and len(entry["why"]) <= 200
    gap = next(m for m in benchmark_json["end_to_end"] if m["name"] == "step_gap_p95_ms")
    assert CELL not in gap["workloads"]  # its long gap is a host-bound env step
    per_layer = {m["name"]: m for m in benchmark_json["per_layer"]}
    assert all(per_layer[name]["workloads"] == [CELL] for name in OWN_READERS)
    listed = sorted(name for name, m in per_layer.items() if CELL in m["workloads"])
    assert listed == sorted(OWN_READERS + ["train_step.device_ms", "train_step.mfu", "train_step.optimizer_ms", "train_step.unscoped_pct",
                                           "device.idle_pct", "device.peak_hbm_gib", "device.idle_unattributed_pct", "loop.train_span_pct",
                                           "env.self_ms"])
    spec, _ = spec_and_adapter(CELL)
    mix = spec["mix"]
    assert (mix["num_envs"], mix["rollout_steps"], mix["episode_unit"], mix["update_epochs"], mix["minibatches"]) == (32, 128, 16, 2, 8)
    assert mix["action"]["n"] == 18992 and mix["own_token_share"] == 0.1 and mix["cut_share"] == pytest.approx(2 / 7)


def test_the_cut_is_the_stated_table_by_eval_shape():
    spec, _ = spec_and_adapter(CELL)
    _, shapes = adapter.program_shapes(spec)
    count = lambda keep: sum(int(np.prod(s)) for n, (s, _) in shapes.items() if keep(n))  # noqa: E731
    assert count(lambda n: n.startswith("layer_0/attn/w_q/")) == count(lambda n: n.startswith("layer_0/attn/w_o/")) == 8388608
    assert count(lambda n: n.startswith("layer_0/attn/w_k/")) == count(lambda n: n.startswith("layer_0/attn/w_v/")) == 1048576
    assert count(lambda n: n.startswith("layer_0/moe/router/")) == 262144 and count(lambda n: n.startswith("layer_0/moe/experts/")) == 75497472
    assert all(count(lambda n, i=i: n.startswith(f"layer_{i}/")) == 94638336 for i in range(5)) and not any(n.startswith("layer_5/") for n in shapes)
    assert count(lambda n: n.startswith(("embed/", "head/"))) == 2 * 38895616 and shapes["value/kernel"][0] == (2048, 1)
    assert count(lambda n: True) == 550987009  # 551.0 M
    kept = adapter.kept_bytes(shapes, spec)
    assert kept["cache"] == 5 * 2 * 32 * 512 * 512 * 4 and kept["total"] / 2**30 == pytest.approx(6.47, abs=0.01)


def test_work_is_counted_from_shapes_and_the_routings_expected_load():
    """By hand at the tests' small size: 4 of 8 experts held with top-2, so one pair a token comes here; blocks of 4
    ids, 2 copies of each action block, minibatches of 2 sequences of 8 blocks."""
    spec, _ = spec_and_adapter(TINY_CELL, TINY_BENCH)
    _, shapes = adapter.program_shapes(spec)
    C, H, KV, D, F, V = 64, 4, 2, 16, 32, 97
    attn, router, experts = C * H * D * 2 + 2 * C * KV * D, C * 8, 3 * C * F * 1.0
    # a block sees (2 + 1) / 2 of its own unit of 2 and, behind it, 2 x (1/2 + 1/4 + ...) of the uncut units: mean over 4 units
    ctx = 1.5 + 2 * np.mean([0, 0.5, 0.75, 0.875])
    assert adapter.mean_context(spec["mix"]) == pytest.approx(ctx)
    pairs = 8 * 4 * 4 * ctx + 8 * 2 * 4 * (4 * ctx + 4)
    per_seq = 3 * 8 * 4 * 2 * (attn + router + experts) + pairs * 2 * H * 2 * D + 8 * (4 * C * V + C)
    act = 12 * 2 * (attn + router + experts) + (16 * ctx + 4 * (4 * ctx + 4) + 4 * (4 * ctx + 4)) * 2 * H * 2 * D + 2 * 4 * C * V + C
    flops = adapter.step_flops(shapes, spec)
    assert flops["total"] == pytest.approx(6.0 * per_seq * 2) and flops["per_env_step"] == pytest.approx(2.0 * act)
    assert adapter.bd_attn_flops(shapes, spec) == pytest.approx(6.0 * 2 * 2 * (96 * attn + pairs * H * 2 * D))
    kernels = 2 * attn * 4 + 2 * 2 * D * 4  # two layers' projections and their q/k norms, float32
    assert adapter.bd_attn_bytes(shapes, spec) == pytest.approx(3.0 * kernels + 3.0 * 2.0 * 96 * 2 * C * 4 * 2)
    assert adapter.expert_flops(shapes, spec, 10.0) == 18.0 * C * F * 10 and adapter.expert_bytes(shapes) == 2 * 4 * 3 * C * F * 4
    params = sum(int(np.prod(s)) * 4 for n, (s, _) in shapes.items() if not n.startswith("embed/"))
    assert adapter.decode_bytes(shapes, spec) == pytest.approx(2 * params + 4 * 4 * 3 * C * 4 + 2 * 4 * 2 * 4 * ctx * 2 * KV * D * 4)
    spec, _ = spec_and_adapter(CELL)
    _, shapes = adapter.program_shapes(spec)
    assert adapter.decode_bytes(shapes, spec) / 1e9 == pytest.approx(4.26, abs=0.02)  # two forwards' parameters and the cache rows in use
    assert adapter.step_flops(shapes, spec)["total"] / 1e12 == pytest.approx(5.07, abs=0.01)


def test_every_rollout_is_tiled_by_whole_episodes_and_no_block_holds_the_mask():
    reset_registry()
    lengths = []
    for rank in range(4):
        env = BlockEpisodesEnv("blk32x128", bench_seed=3000000019 + rank, rank=rank)
        for rollout in range(6):
            ends = env.episode_ends(rollout)
            assert ends[-1] == 128 and all(e % 16 == 0 for e in ends) and ends == sorted(set(ends))
            lengths += list(np.diff([0] + ends))
        assert env._own.max() < 18992 and not np.any(env._own == 18991)
    assert set(lengths) <= {16 * k for k in range(1, 9)} and 2.0 < 24 * 128 / len(lengths) / 16 < 4.5
    env = BlockEpisodesEnv(TINY_BENCH.replace("bd_bench.json", "traffic/blk4x8.json"), bench_seed=7, rank=2)
    assert REGISTRY[2] is env
    obs, _ = env.reset()
    steps, firsts, ids = 0, 0, list(obs["block"])
    for t in range(24):  # three rollouts of 8 blocks
        action = (np.arange(4) * 5 + 3 * t) % 96
        obs, reward, term, trunc, _ = env.step(action)
        steps += 1
        ids += list(obs["block"])
        own = env._own[(env.n - 1) % len(env._own)]
        assert not trunc and obs["block"].shape == (4,) and np.all((obs["block"] == action) | (obs["block"] == own))
        if term:
            firsts += 1
            assert steps in (2, 4, 6, 8) and reward == env.pay(ids) and (t + 1) % 2 == 0
            obs, _ = env.reset()
            steps, ids = 0, list(obs["block"])
        else:
            assert reward == 0.0
        if (t + 1) % 8 == 0:
            assert term  # no episode crosses a rollout boundary
    assert len(env.seen()) == 25 and env.n == 25 + firsts and not any(np.any(b == 96) for b in env.log_block)


def test_a_rollout_from_the_generator_is_what_the_row_check_accepts_and_a_wrong_row_is_seen():
    spec, _ = spec_and_adapter(TINY_CELL, TINY_BENCH)
    cfg, _ = adapter.program_shapes(spec)
    sz = adapter.sizes_for(cfg, 2, 2)
    reset_registry()
    rollout = adapter.rollout_from_generator(spec, 41, sz)
    assert adapter.rollout_rows(rollout, dict(REGISTRY), sz) == (4 * 8, 0)
    assert rollout["dones"][:, -1].all() and rollout["is_first"][:, 0].all() and rollout["dones"].sum() == rollout["is_first"].sum()
    assert rollout["actions"].max() < sz.mask_id and np.all(np.sum(rollout["order"] == 0, -1) == 2)
    rollout["actions"][1, 3, 2] += 1
    rollout["tokens"][3, 7, 0] += 1
    assert adapter.rollout_rows(rollout, dict(REGISTRY), sz) == (4 * 8, 2)


@pytest.mark.parametrize("metric", OWN_READERS)
def test_a_reader_of_this_family_reads_nothing_where_there_is_nothing_to_read(metric):
    """On the parent, in another adapter's cell, or without a capture: no number and no exception."""
    from types import SimpleNamespace

    from perfbench import peaks
    from perfbench.run import metric_reader

    ctx = {"capture": None, "trace": {"programs": {}, "window_s": 0.0}, "trace_dir": None, "window": {"grad_steps": 0, "train_calls": 0, "env_steps": 0, "seconds": 1.0},
           "rehearse": False, "shapes": {}, "spec": {}, "adapter": SimpleNamespace(), "peaks": peaks, "device_kind": "TPU v5 lite", "envs": {}}
    assert metric_reader(metric)(ctx) is None


def test_the_limits_file_says_what_it_was_set_from():
    spec, _ = spec_and_adapter(CELL)
    with open(spec["limits_file"]) as f:
        limits = json.load(f)
    assert set(limits["limits"]) == {"values_gap", "logprobs_gap", "values_worst", "logprobs_worst", "advantages_gap", "loss_gap_policy",
                                     "loss_gap_value", "loss_gap_entropy", "update_gap", "update_mid"}
    assert all(f"{kind} " in limits["set_from"] for kind in adapter.fault_kinds)  # each planted fault's reading is given
    assert "control" in limits["set_from"] and re.search(r"\(my (chip|CPU) runs, PR \d+", limits["set_from"])  # where its readings were made
