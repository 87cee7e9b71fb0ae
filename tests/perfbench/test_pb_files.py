"""The benchmark's data files: every cell composes through the program's own
config system, every named file is there, the peaks table refuses an unknown chip."""
import json
import os
import re

import pytest

import pb_checks
from pb_checks import adapter_names
from pb_helpers import ANY_CELLS, CELLS, PPO_BENCH, PPO_CELL, ROOT, bench, config_files, mix_files

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


def test_every_workloads_list_names_cells_and_names_none_twice(benchmark_json):
    for m in benchmark_json["end_to_end"] + benchmark_json["per_layer"]:
        listed = m.get("workloads")
        assert listed is None or (listed and len(set(listed)) == len(listed) and set(listed) <= set(CELLS)), m["name"]
    assert "workloads" not in next(m for m in benchmark_json["end_to_end"] if m["name"] == "setup_s")  # every cell reports it


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_end_to_end_metric_and_per_layer_metrics_that_move_what_it_reports(cell, benchmark_json):
    """The contract's least for a cell, read as the harness reads it: an end-to-end metric may list its cells too
    (`step_gap_p95_ms` since PR 37), so a cell whose long gap is of another kind reports the others."""
    from perfbench.run import reported_by

    e2e = {m["name"] for m in reported_by(benchmark_json["end_to_end"], cell)}
    assert "setup_s" in e2e and len(e2e) >= 2, e2e
    mine = reported_by(benchmark_json["per_layer"], cell)
    assert mine and all(m["moves"] in e2e for m in mine), [m["name"] for m in mine if m["moves"] not in e2e]
    # the whole step's share of the peak stands beside whatever else the cell reads, moving a metric the cell reports
    assert any("mfu" in re.split(r"[._]", m["name"]) for m in mine)


def test_a_metric_that_lists_its_cells_is_reported_by_those_alone_and_one_that_lists_none_by_all():
    from perfbench.run import reported_by

    entries = [{"name": "a"}, {"name": "b", "workloads": ["x.1"]}, {"name": "c", "workloads": ["x.1", "y.2"]}]
    assert [m["name"] for m in reported_by(entries, "x.1")] == ["a", "b", "c"]
    assert [m["name"] for m in reported_by(entries, "y.2")] == ["a", "c"]
    assert [m["name"] for m in reported_by(entries, "z.3")] == ["a"]


def test_four_chip_cells_stay_within_their_share(benchmark_json):
    four = [w for w in benchmark_json["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(benchmark_json["workloads"]) // 4)


@pytest.mark.parametrize("metric", [m["name"] for m in bench()["per_layer"]])
def test_every_per_layer_metric_has_a_reader_of_its_own(metric):
    from perfbench.run import metric_reader

    assert callable(metric_reader(metric))


@pytest.mark.parametrize("cell,bench_file", ANY_CELLS)
def test_cell_files_load_and_compose(cell, bench_file):
    pb_checks.files_load_and_compose(cell, bench_file)


@pytest.mark.parametrize("cell,bench_file", ANY_CELLS)
def test_cell_has_limits_and_every_limit_names_a_compared_number(cell, bench_file):
    pb_checks.limits_name_compared_numbers(cell, bench_file)


@pytest.mark.parametrize("cell,bench_file", ANY_CELLS)
def test_cell_counts_the_flops_of_training_and_of_acting(cell, bench_file):
    pb_checks.counts_training_and_acting(cell, bench_file)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_keeps_more_than_the_floor_by_eval_shape(cell):
    pb_checks.keeps_more_than_the_floor(cell)


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
    data = ("step_programs", "step_parts", "rehearsal_overrides", "compared_numbers", "fault_kinds")
    assert all(callable(getattr(adapter, name)) for name in adapters.CONTRACT if name not in data)
    assert adapter.step_programs and all(p.startswith("jit_") for p in adapter.step_programs)
    # the step's `jax.named_scope` names: a tuple of names, empty for a step that has none
    assert isinstance(adapter.step_parts, tuple) and len(set(adapter.step_parts)) == len(adapter.step_parts)
    assert all(NAME.match(p) and "/" not in p for p in adapter.step_parts)
    assert all("=" in o for o in adapter.rehearsal_overrides)


@pytest.mark.parametrize("name", adapter_names())
def test_every_adapter_names_the_numbers_it_compares_and_the_faults_it_plants(name):
    """The two names the tests ask any cell's adapter for: what `decide` may
    return, and per fault `faults(kind)` plants the numbers of which one must fail."""
    from perfbench import adapters

    adapter = adapters.load(name)
    assert {"widths_of", "compared_numbers", "fault_kinds"} <= set(adapters.CONTRACT)
    assert adapter.compared_numbers and all(NAME.match(n) for n in adapter.compared_numbers)
    assert "unchanged" in adapter.fault_kinds  # the one fault every training cell can have
    for kind, fails in adapter.fault_kinds.items():
        assert isinstance(fails, tuple) and fails, kind
        assert all(any(n.startswith(p) for n in adapter.compared_numbers) for p in fails), (kind, fails)
        with adapter.faults(kind):  # planted and taken out again: the kind is one `faults` knows
            pass
    with pytest.raises(ValueError):
        with adapter.faults("no_such_fault"):
            pass


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


# where an adapter's algorithm is named besides its adapter and its reference under `references/`: DreamerV3's reference
# stays `perfbench/reference.py` (accepted before the seam), and overrides.py keeps the lazy names for
# `tests/test_train_scopes.py`, which lies outside the benchmark's directories. A new adapter gets no entry here.
ELSEWHERE = {"dreamer_v3": {"perfbench/reference.py", "perfbench/overrides.py"}}
# the names of an algorithm's own modules beside the adapter's name
ALSO = {"dreamer_v3": "|world_model"}


def algorithm_names():
    """Every name an identifier can belong to: the program's algorithms (a directory listing of `sheeprl_tpu/algos`,
    no import) and the adapters there are."""
    algos = os.path.join(ROOT, "sheeprl_tpu", "algos")
    return sorted({n for n in os.listdir(algos) if os.path.isdir(os.path.join(algos, n)) and not n.startswith("_")} | set(adapter_names()))


def names_the_algorithm(text, name, names=None):
    """Whether a text names that algorithm. An identifier belongs to ONE algorithm: around every match of the name
    (or of one of its modules' names) the maximal run of letters, digits and `_` is taken, and it is given to the
    longest of `names` (every algorithm and adapter there is) that begins it: `ppo_recurrent_benchmarks` is `ppo_recurrent`'s and not `ppo`'s,
    `dreamer_v3_XL_crafter` is still `dreamer_v3`'s. A run that no name begins (`world_model`, `make_ppo_agent`) counts
    for the name that matched inside it, as every match did before PR 37."""
    names = algorithm_names() if names is None else names
    pattern = re.compile(name + ALSO.get(name, ""), re.I)
    for run in re.findall(r"[A-Za-z0-9_]+", text):  # a name is letters, digits and `_`: a match lies inside one run
        if pattern.search(run):
            begun = [n for n in names if run.lower().startswith(n)]
            if not begun or max(begun, key=len) == name:
                return True
    return False


@pytest.mark.parametrize("text,name,named", [
    ("ppo_recurrent", "ppo", False),
    ('"exp=ppo_recurrent_benchmarks"', "ppo", False),
    ("perfbench/adapters/ppo_recurrent.py", "ppo", False),
    ("from .adapters import ppo_recurrent as adapter", "ppo", False),
    ('"exp=ppo"', "ppo", True),
    ("algos/ppo/ppo.py", "ppo", True),
    ("exp=ppo_benchmarks", "ppo", True),
    ("exp=ppo_recurrent_benchmarks is not exp=ppo", "ppo", True),   # side by side: the second is `ppo`'s
    ("def make_ppo_agent():", "ppo", True),                        # no algorithm begins the run: the match counts
    ("exp=ppo_recurrent_benchmarks", "ppo_recurrent", True),
    ('"exp=dreamer_v3_XL_crafter"', "dreamer_v3", True),
    ("perfbench/adapters/dreamer_v3.py", "dreamer_v3", True),
    ("DREAMER_V3_OWES", "dreamer_v3", True),
    ("the world_model's scan", "dreamer_v3", True),
    ("exp=p2e_dv3_exploration", "dreamer_v3", False),
    ("p2e_dv3", "ppo", False),
    ("exp=dreamer_v2", "dreamer_v3", False),
    ("sac_ae", "sac", False),
])
def test_an_identifier_belongs_to_one_algorithm(text, name, named):
    """Obstacle 3 of ISSUE 37, as cases. Until then the pattern was the adapter's bare letters, so every file
    under `perfbench/` that named `ppo_recurrent` failed the `ppo` case below."""
    names = ["a2c", "dreamer_v1", "dreamer_v2", "dreamer_v3", "droq", "p2e_dv1", "p2e_dv2", "p2e_dv3", "ppo", "ppo_recurrent",
             "sac", "sac_ae"]
    assert names_the_algorithm(text, name, names) is named
    assert bool(re.search(name + ALSO.get(name, ""), text, re.I)) or not named  # the old pattern never saw less


def test_the_algorithms_an_identifier_can_belong_to_are_the_programs_and_the_adapters():
    names = algorithm_names()
    assert {"ppo", "ppo_recurrent", "dreamer_v3", "p2e_dv3", "sac", "sac_ae"} <= set(names) and set(adapter_names()) <= set(names)
    assert "__pycache__" not in names and "__init__" not in names and "__init__.py" not in names


@pytest.mark.parametrize("name", adapter_names())
def test_only_its_adapter_and_its_reference_name_an_algorithm(name):
    """ISSUE 31's acceptance, for every adapter there is: an algorithm is named
    by its adapter and its reference alone. The data files name their recipe
    (`exp=dreamer_v3_...`) and their adapter; no other code of `perfbench/` does."""
    allowed = {f"perfbench/adapters/{name}.py", f"perfbench/references/{name}.py"} | ELSEWHERE.get(name, set())
    sources, names = list(perfbench_sources()), algorithm_names()
    code = sorted(p for p, text in sources if p.endswith(".py") and names_the_algorithm(text, name, names))
    assert f"perfbench/adapters/{name}.py" in code and set(code) <= allowed, code
    # a data file names the algorithm only where some configuration of the benchmark runs it
    data = sorted(p for p, text in sources if p.endswith(".json") and names_the_algorithm(text, name, names))
    runs_it = any(json.loads(text).get("adapter") == name for p, text in sources if p.startswith("perfbench/configs/"))
    assert runs_it or not data, data
    for shared in ("run.py", "check.py", "taps.py", "rehearse.py", "calibrate.py", "span_reduce.py", "trace_reduce.py", "envs.py", "overrides.py"):
        with open(os.path.join(ROOT, "perfbench", shared)) as f:
            assert "sheeprl_tpu.algos" not in f.read(), shared


def test_the_fixture_of_a_second_algorithm_is_files_alone(benchmark_json):
    from perfbench.run import load_cell, overrides_for
    from sheeprl_tpu.config import compose

    with open(os.path.join(ROOT, PPO_BENCH)) as f:
        fixture = json.load(f)
    assert set(fixture) == set(benchmark_json)  # (that no benchmark holds the fixture's cell: test_pb_run.py, by a run)
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
