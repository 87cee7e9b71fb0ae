"""What holds for ANY cell of any benchmark file, whatever its adapter: plain
functions of `(cell, benchmark_file)` that assert, called by the parametrised
tests over the accepted cells and the fixture's, and by the proof that a cell
of another adapter is files alone (`test_pb_addition.py`). Nothing here knows
an algorithm: what only one adapter's cells keep is in that adapter's own
`test_pb_<adapter>.py`."""
import os

from pb_helpers import BENCH_FILE, ROOT

GIB = 2**30
FLOOR_BYTES = 0.25 * 16 * GIB  # the driver's floor: a quarter of one chip's memory


def spec_and_adapter(cell, benchmark=BENCH_FILE):
    """A cell's files as the harness loads them, and the adapter its configuration names."""
    from perfbench import adapters
    from perfbench.run import load_cell

    spec = load_cell(cell, benchmark)
    return spec, adapters.load(spec["config"]["adapter"])


def files_load_and_compose(cell, benchmark=BENCH_FILE):
    """The configuration and the mix state what the program composes from them."""
    from perfbench.run import overrides_for
    from sheeprl_tpu.config import compose

    spec, adapter = spec_and_adapter(cell, benchmark)
    conf, mix = spec["config"], spec["mix"]
    entry = next(c for c in spec["bench"]["configs"] if c["name"] == spec["cell"]["config"])
    assert conf["source"].startswith("https://") and conf["source"] == entry["source"]
    assert set(conf["reduced"]) == set(conf["reduced_why"]) == set(entry["reduced"])
    cfg = compose("config", overrides_for(spec, 3000000019, False))
    assert conf["precision"].startswith(str(cfg.fabric.precision))
    assert adapter.widths_of(cfg) == conf["widths"]
    assert int(cfg.env.num_envs) == mix["num_envs"] and bool(cfg.env.sync_env)
    assert str(cfg.env.wrapper._target_) == mix["generator"]
    assert not bool(cfg.buffer.checkpoint) and not bool(cfg.checkpoint.save_last) and not bool(cfg.algo.run_test)
    assert 0 <= int(cfg.seed) < 2**31


def limits_name_compared_numbers(cell, benchmark=BENCH_FILE):
    """The limits file is there, names only numbers the adapter's `decide` may
    return, and holds one that a state left unchanged fails."""
    from perfbench.check import load_limits

    spec, adapter = spec_and_adapter(cell, benchmark)
    limits = load_limits(spec["limits_file"])
    assert limits and set(limits) <= set(adapter.compared_numbers), set(limits) - set(adapter.compared_numbers)
    assert any(k.startswith(adapter.fault_kinds["unchanged"]) for k in limits)


def why_names_the_mixs_envs(cell, benchmark=BENCH_FILE):
    spec, _ = spec_and_adapter(cell, benchmark)
    assert f"{spec['mix']['num_envs']} env" in spec["cell"]["why"]
    assert int(spec["mix"]["warmup_train_calls"]) >= 1


def keeps_more_than_the_floor(cell, benchmark=BENCH_FILE):
    """What the cell keeps across calls, by `jax.eval_shape` of the program's
    own build, against the driver's floor. For cells of a benchmark: a fixture
    of the tests reaches none."""
    spec, adapter = spec_and_adapter(cell, benchmark)
    _, shapes = adapter.program_shapes(spec)
    kept = adapter.kept_bytes(shapes, spec)
    assert kept["total"] == sum(v for k, v in kept.items() if k != "total")
    assert kept["total"] >= 1.05 * FLOOR_BYTES, kept


def counts_training_and_acting(cell, benchmark=BENCH_FILE):
    """`train_step.mfu` means one thing in every cell: the adapter counts, from
    the shapes of the program's own build, one gradient step and the player's
    forwards for one env step."""
    spec, adapter = spec_and_adapter(cell, benchmark)
    _, shapes = adapter.program_shapes(spec)
    flops = adapter.step_flops(shapes, spec)
    assert 0.0 < flops["per_env_step"] < flops["total"] < float("inf"), flops


ANY_CELL = (files_load_and_compose, limits_name_compared_numbers, why_names_the_mixs_envs, counts_training_and_acting)


def cells_of(adapter_name, benchmark=BENCH_FILE):
    """The cells of a benchmark file whose configuration names this adapter."""
    from perfbench.run import load_cell, load_json

    names = [w["name"] for w in load_json(ROOT, benchmark)["workloads"]]
    return [n for n in names if load_cell(n, benchmark)["config"]["adapter"] == adapter_name]


def adapter_names():
    """Every adapter there is: `perfbench/adapters/<name>.py`."""
    return sorted(n[:-3] for n in os.listdir(os.path.join(ROOT, "perfbench", "adapters")) if n.endswith(".py") and n != "__init__.py")


def adapter_of_test_file(name):
    """The adapter whose own tests a file of `tests/perfbench/` holds: `test_pb_<adapter>.py` or
    `test_pb_<adapter>_<what>.py`, the longest adapter's name where two fit; None for a test of any cell."""
    fits = [a for a in adapter_names() if name == f"test_pb_{a}.py" or name.startswith(f"test_pb_{a}_")]
    return max(fits, key=len) if fits else None


def capture_for(cell, planes, benchmark=BENCH_FILE):
    """The second reduction of a parsed capture as `run.py` makes it for a run of that cell: the step's programs and
    the step's parts are its adapter's."""
    from perfbench import span_reduce

    _, adapter = spec_and_adapter(cell, benchmark)
    return span_reduce.Capture(planes, adapter.step_programs, adapter.step_parts)


def spans_are_registered_and_the_owed_occur(seen, owed, schemas):
    """What a capture of one loop is held to: every span in it is in the program's table, and every span THAT loop
    owes is in it. Not equality with the table: a span registered for another loop occurs in no run of this one."""
    assert set(seen) <= set(schemas), sorted(set(seen) - set(schemas))
    assert set(owed) <= set(seen), sorted(set(owed) - set(seen))
