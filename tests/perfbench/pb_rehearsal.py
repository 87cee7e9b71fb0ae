"""The PR a `model_config` builder will make, built as a tree: a copy of the
benchmark in which a cell of a SECOND adapter is accepted.

`build(dst)` makes, under `dst`, what such a PR leaves behind: the root's
entries as symlinks (the program, `pyproject.toml`, `tests/conftest.py`),
`perfbench/` and `tests/perfbench/` as copies, and in the copies

* the second family's data files where a PR puts them
  (`perfbench/configs/`, `perfbench/traffic/`, `perfbench/limits/`), made from
  the proof's fixture (`exp=ppo` through `adapters/ppo.py`) under names of
  their own; the configuration's file names a recipe of `ppo_recurrent`
  beside the `ppo` one it runs;
* a per-layer reader of its own (`perfbench/metrics/<READER>.py`), a code file
  that names `ppo_recurrent`, for a span of its own;
* that span registered: `SPAN_SCHEMAS` is the program's, which no benchmark
  PR may edit, so a `conftest.py` at the copy's root adds the name to the
  copy's view of the table, as the PR's edit of `telemetry/schema.py` would.
  No loop of DreamerV3 emits it;
* the root `BENCHMARK.json` grown: the configuration, the cell, the reader's
  entry, the cell's name appended to the `workloads` lists of the per-layer
  metrics it reports, and NOT to `step_gap_p95_ms`'s (an end-to-end metric
  that lists its cells: the new one reports the three others).

`targets(tree)` and `run_over(tree, targets)`: what `test_pb_addition.py` runs
inside that tree, as it stands: every `test_pb_*.py` but the control, whole,
except `test_pb_faults.py`, of which ONE case runs (`ONE_CASE_OF`: five
harness processes of a minute each read nothing that the added cell changes,
and tier-1 has a budget; the case that stays is the fault every adapter has).
`python3 tests/perfbench/pb_rehearsal.py <dst> [<root of another
checkout>]` builds it by hand, from another tree's files where one is given
(the parent's, to see its tests refuse the same PR).
"""
import copy
import glob
import json
import os
import re
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURES = os.path.join("tests", "perfbench", "fixtures")
CONFIG, MIX = "ppo_added", "vec8_added"
CELL = f"{CONFIG}.{MIX}"
SPAN, READER = "Player/prefill", "player.prefill_ms"
# set in the environment of the tests that run over the copy: the copy's own `test_pb_addition.py` builds no copy of the copy
INSIDE = "PB_INSIDE_REHEARSAL"
LEFT_AT_THE_ROOT = {".git", ".xla_cache", ".pytest_cache", ".perfbench_proof", ".hypothesis", "chiprun_out", "logs", "perfbench",
                    "tests", "BENCHMARK.json", "conftest.py"}

# files of which the rehearsal runs one case and not all: the planted faults are five harness processes of the accepted
# cells, a minute each, which see neither the added cell nor the patched span table; `unchanged` is the one fault every
# adapter's contract has. (The harness over the grown root file is driven by `test_pb_run.py` and `test_pb_spans.py`.)
ONE_CASE_OF = {"test_pb_faults.py": "test_fault_makes_the_run_incorrect[unchanged]"}

READER_SOURCE = f'''"""Median duration of `{SPAN}`: the span a sequence policy puts around the
prefill of its cache when it rides the recurrent on-policy loop
(`exp=ppo_recurrent_benchmarks`, `algos/ppo_recurrent`). Nothing to read where
no such span is in the capture."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.span_median_ms(ctx, "{SPAN}")
'''

CONFTEST_SOURCE = f'''"""Written by tests/perfbench/pb_rehearsal.py: the span the added family registers, as its PR's edit of
`sheeprl_tpu/telemetry/schema.py` would have it. No loop of DreamerV3 emits it."""
from sheeprl_tpu.telemetry.schema import SPAN_SCHEMAS

SPAN_SCHEMAS.setdefault("{SPAN}", ("tokens",))
'''


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _dump(obj, *parts):
    os.makedirs(os.path.dirname(os.path.join(*parts)), exist_ok=True)
    with open(os.path.join(*parts), "w") as f:
        json.dump(obj, f, indent=1)


def grown(bench, fixture):
    """The accepted benchmark with the added family's entries: what its PR does to `BENCHMARK.json`, and nothing else."""
    out = copy.deepcopy(bench)
    config = dict(fixture["configs"][0], name=CONFIG, file=f"perfbench/configs/{CONFIG}.json")
    cell = dict(fixture["workloads"][0], name=CELL, config=CONFIG, traffic=MIX)
    out["configs"].append(config)
    out["workloads"].append(cell)
    reports = {m["name"] for m in fixture["per_layer"]}
    assert reports <= {m["name"] for m in out["per_layer"]}
    for m in out["per_layer"]:
        if m["name"] in reports:
            m["workloads"] = m["workloads"] + [CELL]
    out["per_layer"].append({"name": READER, "unit": "ms", "better": "lower", "source": "program_span", "layer": "player",
                             "moves": "env_steps_per_s", "workloads": [CELL]})
    return out


def build(dst, src=ROOT):
    """The tree under `dst` (a new directory); returns the grown benchmark."""
    os.makedirs(os.path.join(dst, "tests"))
    for name in sorted(set(os.listdir(src)) - LEFT_AT_THE_ROOT):
        os.symlink(os.path.join(src, name), os.path.join(dst, name))
    for name in os.listdir(os.path.join(src, "tests")):  # `tests/conftest.py` pins the CPU for whatever runs under `tests/`
        if name.endswith(".py") and not name.startswith("test_"):
            os.symlink(os.path.join(src, "tests", name), os.path.join(dst, "tests", name))
    junk = shutil.ignore_patterns("__pycache__", "*.pyc")
    shutil.copytree(os.path.join(src, "perfbench"), os.path.join(dst, "perfbench"), ignore=junk)
    shutil.copytree(os.path.join(src, "tests", "perfbench"), os.path.join(dst, "tests", "perfbench"), ignore=junk)

    fixture = _load(src, FIXTURES, "ppo_bench.json")
    config = _load(src, fixture["configs"][0]["file"])
    config["name"] = CONFIG
    config["assumed"] = list(config["assumed"]) + [
        "the recipe: exp=ppo, the coupled loop; exp=ppo_recurrent_benchmarks (algos/ppo_recurrent) is the loop a sequence "
        "policy rides and is NOT what this file runs"]
    _dump(config, dst, "perfbench", "configs", f"{CONFIG}.json")
    mix = _load(src, FIXTURES, "traffic", f"{fixture['workloads'][0]['traffic']}.json")
    _dump(dict(mix, name=MIX), dst, "perfbench", "traffic", f"{MIX}.json")
    shutil.copy(os.path.join(src, FIXTURES, "limits", f"{fixture['configs'][0]['name']}.json"),
                os.path.join(dst, "perfbench", "limits", f"{CONFIG}.json"))
    with open(os.path.join(dst, "perfbench", "metrics", f"{READER}.py"), "w") as f:
        f.write(READER_SOURCE)
    with open(os.path.join(dst, "conftest.py"), "w") as f:
        f.write(CONFTEST_SOURCE)
    bench = grown(_load(src, "BENCHMARK.json"), fixture)
    _dump(bench, dst, "BENCHMARK.json")
    return bench


def targets(tree):
    """What the rehearsal runs of a tree: every `test_pb_*.py` but the control (it replays the reference for minutes and
    reads no benchmark file) as a path from the tree's root, a file of `ONE_CASE_OF` as that one case's node id."""
    found = sorted(glob.glob(os.path.join(tree, "tests", "perfbench", "test_pb_*.py")))
    files = [os.path.relpath(f, tree) for f in found if "_control" not in os.path.basename(f)]
    return [f + "::" + ONE_CASE_OF[os.path.basename(f)] if os.path.basename(f) in ONE_CASE_OF else f for f in files]


def run_over(tree, targets, timeout=900):
    """One pytest process inside a rehearsed tree over those of its test files and cases; (return code, summary line,
    end of the output). Deselected: the one check the added cell cannot pass, the floor (an MLP and KBs of state)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", **{INSIDE: "1"})
    for name in ("PYTHONPATH", "JAX_COMPILATION_CACHE_DIR"):
        env.pop(name, None)
    floor = f"tests/perfbench/test_pb_files.py::test_cell_keeps_more_than_the_floor_by_eval_shape[{CELL}]"
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider", "-p", "no:randomly", "-p", "no:xdist", "-m", "not slow",
         "-rs", *targets, "--deselect", floor],
        capture_output=True, text=True, env=env, cwd=tree, timeout=timeout)
    tail = proc.stdout[-6000:]
    return proc.returncode, (tail.strip().splitlines() or [""])[-1], tail


def passed(summary):
    """The count of passes in pytest's last line, where nothing failed and nothing errored; else 0."""
    found = re.search(r"(\d+) passed", summary)
    return int(found.group(1)) if found and "failed" not in summary and "error" not in summary else 0


if __name__ == "__main__":
    build(sys.argv[1], *(os.path.abspath(p) for p in sys.argv[2:3]))
    print(f"built {sys.argv[1]}: cell {CELL}, span {SPAN}, reader {READER}")
