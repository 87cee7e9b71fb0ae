"""The benchmark's data files: every cell composes through the program's own
config system, every named file is there, the peaks table refuses an unknown chip."""
import json
import os
import re

import pytest

from pb_helpers import CELLS, ROOT

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
    assert str(cfg.env.wrapper._target_) == "perfbench.envs.SyntheticEnv"
    assert not bool(cfg.buffer.checkpoint) and not bool(cfg.checkpoint.save_last) and not bool(cfg.algo.run_test)
    assert 0 <= int(cfg.seed) < 2**31


@pytest.mark.parametrize("cell", CELLS)
def test_cell_has_limits_and_every_limit_names_a_compared_number(cell):
    from perfbench.check import GROUPS, load_limits
    from perfbench.run import load_cell

    limits = load_limits(load_cell(cell)["config"]["name"])
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
