"""The PR a `model_config` builder will make, rehearsed with the algorithm that
is there, twice over. (1) A benchmark file that is the accepted one with the
fixture's cell of ANOTHER adapter appended goes through every any-cell check
and through the harness. (2) The PR itself, as a tree (`pb_rehearsal.build`):
a copy of the benchmark in which such a cell is ACCEPTED in the root
`BENCHMARK.json`, with its files under `perfbench/` and a span of its own
registered, over which every test file of `tests/perfbench/` runs as it
stands, with no edit to any file under `perfbench/` or `tests/perfbench/`."""
import glob
import json
import os
import re

import pytest

import pb_checks
import pb_rehearsal
from pb_helpers import CELLS, PPO_BENCH, PPO_CELL, ROOT, bench, run_harness

INSIDE = bool(os.environ.get(pb_rehearsal.INSIDE))
PROOFS = "ppo"  # the adapter of the proof's cells: an MLP and KBs of state, which reach no floor


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


def test_every_any_cell_check_holds_for_every_cell_of_the_grown_file(grown):
    """Whatever adapters the accepted cells have: the grown file keeps each adapter's accepted cells and gives the
    proof's adapter one more. (Until PR 36 the last line held `cells_of("dreamer_v3", path)` to EVERY accepted
    cell, which one accepted cell of another adapter makes false for good.)"""
    path, bench_json = grown
    names = [w["name"] for w in bench_json["workloads"]]
    assert names == CELLS + [PPO_CELL]
    for cell in names:
        for check in pb_checks.ANY_CELL:
            check(cell, path)
    proofs = pb_checks.cells_of(PROOFS, path)
    for cell in CELLS:
        if cell not in proofs:  # a cell of the proof's adapter reaches no floor, accepted (in a rehearsal) or not
            pb_checks.keeps_more_than_the_floor(cell, path)
    assert proofs == pb_checks.cells_of(PROOFS) + [PPO_CELL]
    assert pb_checks.cells_of("dreamer_v3", path) == pb_checks.cells_of("dreamer_v3")
    adapters = {pb_checks.spec_and_adapter(c)[0]["config"]["adapter"] for c in CELLS}
    assert sorted(c for a in adapters for c in pb_checks.cells_of(a)) == sorted(CELLS)  # every accepted cell is some adapter's


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


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """The rehearsed PR's tree and one run of the benchmark's tests over it, shared by the tests below."""
    if INSIDE:
        pytest.skip("inside the rehearsal: the copy builds no copy of itself")
    copy = str(tmp_path_factory.mktemp("rehearsed") / "tree")
    grown_bench = pb_rehearsal.build(copy)
    targets = pb_rehearsal.targets(copy)
    return copy, grown_bench, targets, pb_rehearsal.run_over(copy, targets)


def test_the_rehearsed_tree_is_what_such_a_pr_leaves(rehearsed):
    """Where the PR stands: the second adapter's cell is in the root's file, its data files are under `perfbench/`,
    one of them and one code file name `ppo_recurrent` beside `ppo`, a span is registered that is not the program's,
    and `step_gap_p95_ms` keeps the list of the cells whose long gap is of the accepted kind."""
    copy, grown_bench, _, _ = rehearsed
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        assert json.load(f) == grown_bench
    accepted = bench()
    assert [w["name"] for w in grown_bench["workloads"]] == CELLS + [pb_rehearsal.CELL]
    assert len(grown_bench["per_layer"]) == len(accepted["per_layer"]) + 1 and grown_bench["end_to_end"] == accepted["end_to_end"]
    gap = next(m for m in grown_bench["end_to_end"] if m["name"] == "step_gap_p95_ms")
    assert pb_rehearsal.CELL not in gap["workloads"] and set(gap["workloads"]) <= set(CELLS)
    for sub in ("configs", "limits"):
        assert os.path.isfile(os.path.join(copy, "perfbench", sub, pb_rehearsal.CONFIG + ".json"))
    assert os.path.isfile(os.path.join(copy, "perfbench", "traffic", pb_rehearsal.MIX + ".json"))
    for rel in (f"perfbench/configs/{pb_rehearsal.CONFIG}.json", f"perfbench/metrics/{pb_rehearsal.READER}.py"):
        with open(os.path.join(copy, rel)) as f:
            text = f.read()
        assert re.search(r"\bppo_recurrent", text) and (rel.endswith(".py") or re.search(r"\bexp=ppo\b", text)), rel
    from sheeprl_tpu.telemetry.schema import SPAN_SCHEMAS

    assert pb_rehearsal.SPAN not in SPAN_SCHEMAS and pb_rehearsal.SPAN in open(os.path.join(copy, "conftest.py")).read()
    assert not os.path.islink(os.path.join(copy, "perfbench")) and not os.path.islink(os.path.join(copy, "tests", "perfbench"))
    assert os.path.islink(os.path.join(copy, "sheeprl_tpu"))  # the program is the tree's, untouched


def test_the_benchmarks_own_tests_pass_over_the_rehearsed_tree(rehearsed):
    """The tests a later PR may not edit, run as they stand where that PR
    stands. The proof of PR 32 had three blind spots, which is why PR 36's cell
    could not be added: (i) it left out this file and every file that drives a
    harness process, where two of the three refusing tests were; (ii) the
    second family's files lay under `tests/perfbench/fixtures/`, where the
    test that walks `perfbench/` never met a data file naming a second
    algorithm; (iii) the second family registered no span. Here every
    `test_pb_*.py` but the minutes-long control runs, the harness's drivers and
    this file's own any-cell test included, over a tree in which the cell is
    accepted; of the planted faults one case of five (`pb_rehearsal.ONE_CASE_OF`
    says why: tier-1's budget). Left out: the one check the proof's cell cannot
    pass, the floor (an MLP and KBs of state), which is how it is known that
    the added cell was seen at all: exactly one case is deselected. Skipped
    inside: the two tests that would build a copy of the copy."""
    copy, _, targets, (rc, summary, tail) = rehearsed
    names = [os.path.basename(t.split("::")[0]) for t in targets]
    mine = sorted(os.path.basename(f) for f in _files() if os.path.basename(f).startswith("test_pb_") and "_control" not in f)
    assert names == mine and len(names) >= 10 and os.path.basename(__file__) in names
    assert [t for t in targets if "::" in t] == [f"tests/perfbench/{f}::{case}" for f, case in pb_rehearsal.ONE_CASE_OF.items()]
    assert rc == 0, tail
    assert pb_rehearsal.passed(summary) >= 186 and "1 deselected" in summary and "2 skipped" in summary, summary
    assert tail.count("inside the rehearsal") == 2, tail  # what the two skipped are


def test_no_other_test_imports_an_adapters_module_or_finds_a_cell_by_its_place_or_writes_out_its_name():
    """By grep: a test that holds for any cell reaches an adapter through
    `adapters.load` alone, and names the cells it drives by `pb_helpers`' two
    constants: no accepted cell's name is written out anywhere else, where it
    could stand in a list that a longer `workloads` no longer equals. Only an
    adapter's own tests (`test_pb_<adapter>*.py`, for every adapter under
    `perfbench/adapters/`) import its module."""
    offending = {}
    cells = "|".join(re.escape(c) for c in CELLS)
    for path in _files():
        name = os.path.basename(path)
        if pb_checks.adapter_of_test_file(name) or name == os.path.basename(__file__):
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


@pytest.mark.parametrize("name,adapter", [
    ("test_pb_dreamer_v3.py", "dreamer_v3"), ("test_pb_dreamer_v3_control.py", "dreamer_v3"), ("test_pb_ppo.py", "ppo"),
    ("test_pb_ppo_faults.py", "ppo"), ("test_pb_files.py", None), ("test_pb_spans.py", None), ("pb_helpers.py", None),
    ("test_pb_ppox.py", None), ("dreamer_v3_notes.py", None)])
def test_an_adapters_own_tests_are_told_by_the_adapters_file_not_by_one_adapters_letters(name, adapter):
    assert pb_checks.adapter_of_test_file(name) == adapter
