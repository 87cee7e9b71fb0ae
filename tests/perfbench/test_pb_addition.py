"""The PR a `model_config` builder will make, rehearsed with the algorithm that
is there: a benchmark file that is the accepted one with a cell of ANOTHER
adapter appended goes through every any-cell check, through the harness, and
through the benchmark's own tests run over that file, with no edit to any file
under `perfbench/` or `tests/perfbench/`."""
import glob
import json
import os
import re
import subprocess
import sys

import pytest

import pb_checks
from pb_helpers import CELLS, PPO_BENCH, PPO_CELL, ROOT, bench, run_harness


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """The accepted benchmark with the fixture's configuration and cell added, and the cell's name in the `workloads`
    lists of the per-layer metrics the fixture reports: what such a PR does to `BENCHMARK.json`, and nothing else."""
    with open(os.path.join(ROOT, PPO_BENCH)) as f:
        fixture = json.load(f)
    grown = bench()
    grown["configs"] += fixture["configs"]
    grown["workloads"] += fixture["workloads"]
    reports = {m["name"] for m in fixture["per_layer"]}
    for m in grown["per_layer"]:
        if m["name"] in reports:
            m["workloads"] = m["workloads"] + [PPO_CELL]
    assert reports <= {m["name"] for m in grown["per_layer"]}
    path = tmp_path_factory.mktemp("grown") / "BENCHMARK.json"
    path.write_text(json.dumps(grown))
    return str(path), grown


def test_every_any_cell_check_holds_for_all_three_cells_of_the_grown_file(grown):
    path, bench_json = grown
    names = [w["name"] for w in bench_json["workloads"]]
    assert names == CELLS + [PPO_CELL]
    for cell in names:
        for check in pb_checks.ANY_CELL:
            check(cell, path)
    for cell in CELLS:  # the fixture's cell reaches no floor: an MLP and KBs of state
        pb_checks.keeps_more_than_the_floor(cell, path)
    assert pb_checks.cells_of("ppo", path) == [PPO_CELL] and pb_checks.cells_of("dreamer_v3", path) == CELLS


def test_the_added_cell_runs_through_the_harness_from_the_grown_file_and_is_correct(grown):
    path, _ = grown
    rc, out, err = run_harness("--benchmark", path, "--workload", PPO_CELL, "--seed", "3000000023", "--seconds", "2",
                               "--trace", "1", "--rehearse-cpu")
    assert rc == 0, err[-3000:]
    line = json.loads(out[-1])
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert "algo=ppo" in err and "trace reduced" in err
    assert set(line["compared"]) <= pb_checks.spec_and_adapter(PPO_CELL, path)[1].compared_numbers


def _files():
    return sorted(glob.glob(os.path.join(ROOT, "tests", "perfbench", "*.py")))


def test_the_benchmarks_own_tests_pass_over_the_grown_file(grown):
    """The tests a later PR may not edit, run as they stand over the file it
    will have made: `PB_BENCHMARK` names the grown file to `pb_helpers.bench`, so
    every whole-file test and every test parametrised over the cells sees three
    cells and the longer `workloads` lists. Run: every test file that drives no
    harness process (those name the cell they drive; the added cell's run is the
    test above). Left out: the one check the fixture's cell cannot pass, the
    floor (an MLP and KBs of state), which is how it is known that the added
    cell was seen at all: exactly one case is deselected."""
    path, _ = grown
    files = [f for f in _files() if os.path.basename(f).startswith("test_pb_") and "run_harness" not in open(f).read()
             and "_control" not in f]  # (a control replays the reference for minutes and reads no benchmark file)
    assert len(files) >= 6 and all(os.path.basename(f) != os.path.basename(__file__) for f in files)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PB_BENCHMARK=path)
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider", "-p", "no:randomly", "-p", "no:xdist", *files, "--deselect",
         f"tests/perfbench/test_pb_files.py::test_cell_keeps_more_than_the_floor_by_eval_shape[{PPO_CELL}]"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    tail = proc.stdout[-4000:]
    assert proc.returncode == 0, tail
    summary = tail.strip().splitlines()[-1]
    assert " passed" in summary and "1 deselected" in summary and "failed" not in summary and "error" not in summary, summary
    assert int(re.search(r"(\d+) passed", summary).group(1)) >= 100


def test_no_other_test_imports_an_adapters_module_or_finds_a_cell_by_its_place_or_writes_out_its_name():
    """By grep: a test that holds for any cell reaches an adapter through
    `adapters.load` alone, and names the cells it drives by `pb_helpers`' two
    constants: no accepted cell's name is written out anywhere else, where it
    could stand in a list that a longer `workloads` no longer equals. Only an
    adapter's own tests (`*dreamer_v3*`) import its module."""
    offending = {}
    cells = "|".join(re.escape(c) for c in CELLS)
    for path in _files():
        name = os.path.basename(path)
        if "dreamer_v3" in name or name == os.path.basename(__file__):
            continue
        with open(path) as f:
            text = f.read()
        if name == "pb_helpers.py":  # XL_CELL and L_CELL are said there, once
            text = re.sub(r'XL_CELL, L_CELL = "[^"]+", "[^"]+"\n', "", text, count=1)
        found = re.findall(cells + r"|perfbench\.adapters\.\w+|from perfbench\.adapters import|(?<![A-Z_])CELLS\[|perfbench\.work\b"
                           r"|from perfbench import[^\n]*\bwork\b", text)
        if name == "test_pb_work.py":  # work.py's own hand-worked values
            found = [x for x in found if "work" not in x]
        if found:
            offending[name] = found
    assert offending == {}
