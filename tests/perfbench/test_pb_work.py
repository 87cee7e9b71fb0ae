"""work.py against hand-worked values, and each cell's kept bytes against the driver's floor."""
import numpy as np
import pytest

from perfbench import work

from pb_helpers import CELLS

GIB = 2**30
FLOOR_BYTES = 0.25 * 16 * GIB  # the driver's floor: a quarter of one chip's memory


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


def test_gather_bytes_of_a_hand_worked_row():
    items = {"rgb": ((64, 64, 3), "uint8"), "actions": ((17,), "float32"), "rewards": ((1,), "float32")}
    assert work.row_bytes(items) == 12288 + 68 + 4
    assert work.gather_bytes(items, 1, 64, 16) == 1024 * (2 * 12360 + 4)


def test_ring_rows_are_what_the_issue_reckoned():
    from perfbench.envs import load_mix

    assert work.row_bytes(work.ring_items(load_mix("crafter"), 17)) == 12376
    assert work.row_bytes(work.ring_items(load_mix("navigate4"), 10)) == 12348


@pytest.mark.parametrize("cell", CELLS)
def test_cell_keeps_more_than_the_floor_by_eval_shape(cell):
    """state + ring >= floor, over `jax.eval_shape` of the program's own build_agent."""
    from perfbench import adapters
    from perfbench.run import load_cell

    spec = load_cell(cell)
    mix = spec["mix"]
    adapter = adapters.load(spec["config"]["adapter"])
    cfg, shapes = adapter.program_shapes(spec)
    actions = int(mix["action"]["n"])
    kept = adapter.kept_bytes(shapes, spec)
    assert kept == work.kept_bytes(shapes, mix, int(cfg.buffer.size), actions)
    assert kept["ring"] == int(cfg.buffer.size) * mix["num_envs"] * work.row_bytes(work.ring_items(mix, actions))
    assert kept["ring"] <= float(cfg.buffer.device_cache_max_bytes)  # so that `auto` puts the ring on the chip
    assert kept["total"] >= 1.05 * FLOOR_BYTES, kept


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
    assert adapter.step_flops(shapes, spec) == {"total": 6.0 * macs * 32}
    values = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert adapter.kept_bytes(shapes, spec) == {"params": 4.0 * values, "adam": 8.0 * values, "total": 12.0 * values}
    dv3 = adapters.load("dreamer_v3")
    spec = load_cell(CELLS[0])
    _, shapes = dv3.program_shapes(spec)
    w = spec["config"]["widths"]
    assert dv3.step_flops(shapes, spec) == work.train_step_flops(shapes, w["per_rank_sequence_length"], w["per_rank_batch_size"], w["horizon"])
    assert dv3.step_flops(shapes, spec)["total"] == pytest.approx(8.86e12, rel=0.005)  # PERF.md section 4
