"""What only DreamerV3's cells keep, over the cells whose configuration names
the `dreamer_v3` adapter: the sizes the limits were calibrated at, the ring on
the chip, the replay ratio. What holds for any cell is in `pb_checks.py`; an
adapter that is added brings a file like this one for what only it knows."""
import pytest

from pb_checks import cells_of
from pb_helpers import BENCH_FILE, L_CELL, XL_CELL
from perfbench import work
from perfbench.adapters import dreamer_v3 as dv3

DV3_CELLS = cells_of("dreamer_v3")


def test_the_accepted_cells_are_dreamer_v3s():
    assert {XL_CELL, L_CELL} <= set(DV3_CELLS)


@pytest.mark.parametrize("cell", DV3_CELLS)
def test_cell_runs_at_the_sizes_its_limits_were_set_at(cell):
    from perfbench.run import load_cell, overrides_for
    from sheeprl_tpu.config import compose

    spec = load_cell(cell, BENCH_FILE)
    conf, mix = spec["config"], spec["mix"]
    cfg = compose("config", overrides_for(spec, 3000000019, False))
    assert (int(cfg.algo.per_rank_sequence_length), int(cfg.algo.per_rank_batch_size)) == (64, 16)
    assert str(cfg.fabric.precision) == "32-true"
    assert int(cfg.buffer.size) == conf["buffer.size"] and str(cfg.buffer.device_cache) == "auto"
    assert float(cfg.algo.replay_ratio) == mix["replay_ratio"] and int(cfg.algo.learning_starts) == mix["learning_starts"]
    assert mix["generator"] == "perfbench.envs.SyntheticEnv"
    assert mix["episode_steps"] == 500 and mix["warmup_train_calls"] >= dv3.CHECK_STEPS + 1


@pytest.mark.parametrize("cell", DV3_CELLS)
def test_cell_keeps_its_ring_on_the_chip(cell):
    """The ring's bytes, by `jax.eval_shape` of the program's own build_agent, and that `auto` puts it in HBM."""
    from perfbench.run import load_cell

    spec = load_cell(cell, BENCH_FILE)
    mix = spec["mix"]
    cfg, shapes = dv3.program_shapes(spec)
    actions = int(mix["action"]["n"])
    kept = dv3.kept_bytes(shapes, spec)
    assert kept == work.kept_bytes(shapes, mix, int(cfg.buffer.size), actions)
    assert kept["ring"] == int(cfg.buffer.size) * mix["num_envs"] * work.row_bytes(work.ring_items(mix, actions))
    assert kept["ring"] <= float(cfg.buffer.device_cache_max_bytes)  # so that `auto` puts the ring on the chip

