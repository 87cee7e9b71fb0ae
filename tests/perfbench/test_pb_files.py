"""The benchmark's data files: every cell composes through the program's own
config system, every named file is there, the peaks table refuses an unknown chip."""
import json
import os
import re

import pytest

from pb_helpers import CELLS, PPO_BENCH, PPO_CELL, ROOT, config_files, mix_files

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_has_exactly_the_contract_keys(benchmark_json):
    assert set(benchmark_json) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= benchmark_json["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1 for m in benchmark_json["end_to_end"])
    for m in benchmark_json["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"} and m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    e2e = {m["name"] for m in benchmark_json["end_to_end"]}
    for m in benchmark_json["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


def test_every_name_keeps_to_the_contracts_alphabet(benchmark_json):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in benchmark_json[k]]
    names += [w["traffic"] for w in benchmark_json["workloads"]] + [r for c in benchmark_json["configs"] for r in c["reduced"]]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    assert len({m["name"] for m in benchmark_json["end_to_end"] + benchmark_json["per_layer"]}) == len(
        benchmark_json["end_to_end"] + benchmark_json["per_layer"])


def test_four_chip_cells_stay_within_their_share(benchmark_json):
    four = [w for w in benchmark_json["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(benchmark_json["workloads"]) // 4)


@pytest.mark.parametrize("metric", [m["name"] for m in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["per_layer"]])
def test_every_per_layer_metric_has_a_reader_of_its_own(metric):
    from perfbench.run import metric_reader

    assert callable(metric_reader(metric))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load_and_compose(cell):
    from perfbench.run import load_cell, overrides_for
    from sheeprl_tpu.config import compose

    spec = load_cell(cell)
    conf, mix = spec["config"], spec["mix"]
    assert conf["source"].startswith("https://") and conf["precision"].startswith("32-true")
    assert set(conf["reduced"]) == set(conf["reduced_why"])
    cfg = compose("config", overrides_for(spec, 3000000019, False))
    w = conf["widths"]
    assert int(cfg.algo.dense_units) == w["dense_units"] and int(cfg.algo.mlp_layers) == w["mlp_layers"]
    assert int(cfg.algo.world_model.recurrent_model.recurrent_state_size) == w["recurrent_state_size"]
    assert int(cfg.algo.world_model.encoder.cnn_channels_multiplier) == w["cnn_channels_multiplier"]
    assert (int(cfg.algo.per_rank_sequence_length), int(cfg.algo.per_rank_batch_size)) == (64, 16)
    assert int(cfg.algo.horizon) == w["horizon"] and str(cfg.fabric.precision) == "32-true"
    assert int(cfg.buffer.size) == conf["buffer.size"] and str(cfg.buffer.device_cache) == "auto"
    assert int(cfg.env.num_envs) == mix["num_envs"] and float(cfg.algo.replay_ratio) == mix["replay_ratio"]
    assert int(cfg.algo.learning_starts) == mix["learning_starts"] and bool(cfg.env.sync_env)
    assert str(cfg.env.wrapper._target_) == "perfbench.envs.SyntheticEnv" == mix["generator"]
    assert not bool(cfg.buffer.checkpoint) and not bool(cfg.checkpoint.save_last) and not bool(cfg.algo.run_test)
    assert 0 <= int(cfg.seed) < 2**31


@pytest.mark.parametrize("cell", CELLS)
def test_cell_has_limits_and_every_limit_names_a_compared_number(cell):
    from perfbench.adapters.dreamer_v3 import GROUPS
    from perfbench.check import load_limits
    from perfbench.run import load_cell

    limits = load_limits(load_cell(cell)["limits_file"])
    known = {f"{kind}_{g}" for g in GROUPS for kind in ("loss1_gap", "loss_gap", "grad_gap", "grad_mid", "update_gap", "update_mid")}
    assert limits and set(limits) <= known
    assert any(k.startswith("update_") for k in limits)  # a state left unchanged has to fail something


def test_peaks_lookup_by_device_kind_and_unknown_kind_raises():
    from perfbench import peaks

    v5e = peaks.lookup("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks.lookup("cpu")
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9")


@pytest.mark.parametrize("path", config_files(), ids=os.path.basename)
def test_every_configuration_names_an_adapter_that_exports_the_whole_contract(path):
    from perfbench import adapters

    with open(path) as f:
        conf = json.load(f)
    adapter = adapters.load(conf["adapter"])
    assert os.path.isfile(os.path.join(ROOT, "perfbench", "adapters", conf["adapter"] + ".py"))
    assert all(hasattr(adapter, name) for name in adapters.CONTRACT)
    assert all(callable(getattr(adapter, name)) for name in adapters.CONTRACT if name not in ("step_programs", "rehearsal_overrides"))
    assert adapter.step_programs and all(p.startswith("jit_") for p in adapter.step_programs)
    assert all("=" in o for o in adapter.rehearsal_overrides)


def test_an_adapter_that_lacks_part_of_the_contract_is_refused(tmp_path, monkeypatch):
    from perfbench import adapters

    (tmp_path / "half.py").write_text("step_programs = ('jit_x',)\n")
    monkeypatch.setattr(adapters, "__path__", list(adapters.__path__) + [str(tmp_path)])
    with pytest.raises(AttributeError, match="installed"):
        adapters.load("half")
    with pytest.raises(ImportError):
        adapters.load("no_such_algorithm")


@pytest.mark.parametrize("path", mix_files(), ids=os.path.basename)
def test_every_mix_names_a_generator_that_imports(path):
    from perfbench.envs import generator_of

    with open(path) as f:
        mix = json.load(f)
    assert isinstance(generator_of(mix), type) and mix["name"] == os.path.basename(path)[:-5]


def perfbench_sources():
    for dirpath, _, names in os.walk(os.path.join(ROOT, "perfbench")):
        for name in names:
            if name.endswith((".py", ".json")):
                path = os.path.join(dirpath, name)
                with open(path) as f:
                    yield os.path.relpath(path, ROOT), f.read()


def test_only_its_adapter_its_reference_and_nothing_else_of_perfbench_mentions_ppo():
    mentions = sorted(p for p, text in perfbench_sources() if re.search(r"ppo", text, re.I))
    assert mentions == ["perfbench/adapters/ppo.py", "perfbench/references/ppo.py"]


def test_only_its_adapter_and_its_reference_name_dreamer_v3s_modules():
    """ISSUE 31's acceptance: whatever belongs to the DreamerV3 family is behind
    the seam. The data files name their recipe (`exp=dreamer_v3_...`) and their
    adapter; no other code does."""
    # overrides.py: one lazy name kept for `tests/test_train_scopes.py`, which lies outside the benchmark's directories
    allowed = {"perfbench/adapters/dreamer_v3.py", "perfbench/reference.py", "perfbench/overrides.py"}
    code = sorted(p for p, text in perfbench_sources() if p.endswith(".py") and re.search(r"dreamer_v3|world_model", text))
    assert set(code) <= allowed, code
    for name in ("run.py", "check.py", "taps.py", "rehearse.py", "calibrate.py", "span_reduce.py", "trace_reduce.py", "envs.py", "overrides.py"):
        with open(os.path.join(ROOT, "perfbench", name)) as f:
            assert "sheeprl_tpu.algos" not in f.read(), name


def test_the_fixture_of_a_second_algorithm_is_files_alone(benchmark_json):
    from perfbench.run import load_cell, overrides_for
    from sheeprl_tpu.config import compose

    with open(os.path.join(ROOT, PPO_BENCH)) as f:
        fixture = json.load(f)
    assert set(fixture) == set(benchmark_json) and PPO_CELL not in CELLS
    spec = load_cell(PPO_CELL, PPO_BENCH)
    assert spec["config"]["adapter"] == "ppo" and spec["mix"]["generator"] == "perfbench.envs.VectorEnv"
    assert spec["limits_file"].endswith("tests/perfbench/fixtures/limits/ppo_tiny.json") and os.path.isfile(spec["limits_file"])
    cfg = compose("config", overrides_for(spec, 2**31 + 7, False))
    assert str(cfg.algo.name) == "ppo" and str(cfg.env.wrapper._target_) == "perfbench.envs.VectorEnv"
    assert str(cfg.env.wrapper.mix) == "tests/perfbench/fixtures/traffic/vec8.json"
    w = spec["config"]["widths"]
    assert (int(cfg.algo.rollout_steps), int(cfg.algo.per_rank_batch_size), int(cfg.algo.update_epochs)) == (
        w["rollout_steps"], w["per_rank_batch_size"], w["update_epochs"]) == (16, 32, 1)
    assert int(cfg.algo.dense_units) == w["dense_units"] and int(cfg.env.num_envs) == spec["mix"]["num_envs"] == 2
