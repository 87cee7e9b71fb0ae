"""work.py and each adapter's count of its step against hand-worked values, and what `train_step.mfu` makes of them."""
import numpy as np
import pytest

from perfbench import work

from pb_helpers import XL_CELL


def test_flops_of_a_hand_worked_tiny_model():
    f32 = np.float32
    shapes = {
        "wm/encoder/DV3CNNEncoder_0/conv_0/kernel": ((4, 4, 3, 2), f32),        # 8x8 image -> 4x4: 16 * 96 MACs
        "wm/rssm/recurrent_model/mlp/kernel": ((5, 3), f32),                      # 15 MACs
        "wm/rssm/representation/logits/kernel": ((3, 4), f32),                    # 12 MACs
        "wm/observation_model/DV3CNNDecoder_0/to_obs/kernel": ((4, 4, 3, 2), f32),  # 4x4 in -> 8x8: 16 * 96 MACs
        "wm/reward/out/kernel": ((3, 2), f32),                                    # 6 MACs
        "actor/head_0/kernel": ((3, 2), f32),                                     # 6 MACs
        "critic/out/kernel": ((3, 2), f32),
        "target_critic/out/kernel": ((3, 2), f32),
        "actor/head_0/bias": ((2,), f32),                                         # no multiply
    }
    T, B, H = 2, 3, 4
    rows = T * B
    got = work.train_step_flops(shapes, T, B, H, image_side=8)
    assert got["wm.encoder"] == 6 * 16 * 96 * rows
    assert got["wm.observation_model"] == 6 * 16 * 96 * rows
    assert got["wm.rssm"] == 6 * (15 + 12) * rows
    assert got["imagination.rssm"] == 2 * 15 * H * rows          # the posterior head is not imagined
    assert got["wm.reward"] == 6 * 6 * rows and got["imagination.heads"] == 2 * 6 * (H + 1) * rows
    assert got["actor"] == 6 * 6 * (H + 1) * rows
    assert got["critic"] == (2 * 6 * (H + 1) + 4 * 6 * H + 2 * 6 * H) * rows
    assert got["total"] == sum(v for k, v in got.items() if k != "total")
    # the player's forward for one env step: one row through the encoder, the recurrent model, the posterior head and the
    # actor; neither the prior's head, the decoder, the reward head nor the critics
    assert work.act_flops(shapes, image_side=8) == 2 * (16 * 96 + 15 + 12 + 6)


def test_gather_bytes_of_a_hand_worked_row():
    items = {"rgb": ((64, 64, 3), "uint8"), "actions": ((17,), "float32"), "rewards": ((1,), "float32")}
    assert work.row_bytes(items) == 12288 + 68 + 4
    assert work.gather_bytes(items, 1, 64, 16) == 1024 * (2 * 12360 + 4)


def test_ring_rows_are_what_the_issue_reckoned():
    from perfbench.envs import load_mix

    assert work.row_bytes(work.ring_items(load_mix("crafter"), 17)) == 12376
    assert work.row_bytes(work.ring_items(load_mix("navigate4"), 10)) == 12348


def test_the_whole_steps_flops_and_kept_bytes_come_from_the_cells_adapter():
    """`train_step.mfu` stands in every cell under the one name: each adapter counts its own step."""
    from pb_helpers import PPO_BENCH, PPO_CELL
    from perfbench import adapters
    from perfbench.run import load_cell

    spec = load_cell(PPO_CELL, PPO_BENCH)
    adapter = adapters.load(spec["config"]["adapter"])
    _, shapes = adapter.program_shapes(spec)
    # encoder 8x64 + 64x64 + 64x64, two trunks of 64x64 + 64x64, the critic's 64x1, the head's 64x4: multiply-adds a row
    macs = 8 * 64 + 2 * 64 * 64 + 2 * (2 * 64 * 64) + 64 + 64 * 4
    # one gradient step is a minibatch of 32 rows forward and backward; one env step is the player's forward over one row
    assert adapter.step_flops(shapes, spec) == {"total": 6.0 * macs * 32, "per_env_step": 2.0 * macs}
    values = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert adapter.kept_bytes(shapes, spec) == {"params": 4.0 * values, "adam": 8.0 * values, "total": 12.0 * values}
    dv3 = adapters.load("dreamer_v3")
    spec = load_cell(XL_CELL)
    _, shapes = dv3.program_shapes(spec)
    w = spec["config"]["widths"]
    got = dv3.step_flops(shapes, spec)
    assert got == {**work.train_step_flops(shapes, w["per_rank_sequence_length"], w["per_rank_batch_size"], w["horizon"]),
                   "per_env_step": work.act_flops(shapes)}
    assert got["total"] == pytest.approx(8.86e12, rel=0.005)  # PERF.md section 4
    # one row through the 108 M values the player reads (432 MB, PERF.md section 5): 96.5 M multiply-adds in its matmuls and
    # 231.2 M in the four convs at their output sizes; two env steps a gradient step are 0.015 % of the window's FLOPs
    assert got["per_env_step"] == pytest.approx(2 * (96.5e6 + 231.2e6), rel=0.005) and 2 * got["per_env_step"] < 2e-4 * got["total"]


def test_the_whole_steps_share_of_the_peak_counts_training_and_acting():
    """`train_step.mfu` by hand: gradient steps x 'total' plus env steps x 'per_env_step', over window x peak."""
    from types import SimpleNamespace

    from perfbench import peaks
    from perfbench.run import metric_reader

    read = metric_reader("train_step.mfu")
    flops = {"total": 4e12, "per_env_step": 0.0}
    ctx = {"window": {"grad_steps": 10, "env_steps": 2000, "seconds": 5.0}, "rehearse": False, "shapes": {}, "spec": {},
           "adapter": SimpleNamespace(step_flops=lambda shapes, spec: flops), "peaks": peaks, "device_kind": "TPU v5 lite"}
    assert read(ctx) == pytest.approx(100.0 * 40e12 / (5.0 * 197e12))
    flops["per_env_step"] = 3e10
    assert read(ctx) == pytest.approx(100.0 * (40e12 + 60e12) / (5.0 * 197e12))
    assert read({**ctx, "rehearse": True}) is None  # never a share of a peak from a CPU run
    assert read({**ctx, "window": {"grad_steps": 0, "env_steps": 2000, "seconds": 5.0}}) is None
