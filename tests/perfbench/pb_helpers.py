"""Shared by the perfbench tests: the cells by name, and one harness process on the CPU."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


# the benchmark file every test here reads: the root's. (`test_pb_addition.py` runs these very tests over a tree whose
# root file has grown by a cell: there `ROOT` is that tree)
BENCH_FILE = "BENCHMARK.json"


def bench():
    with open(os.path.join(ROOT, BENCH_FILE)) as f:
        return json.load(f)


CELLS = [w["name"] for w in bench()["workloads"]]
# the cells a test drives are found by name, never by their place in the list: a later PR may add one anywhere
XL_CELL, L_CELL = "dv3_xl.crafter", "dv3_l.navigate4"
# a second algorithm through the harness's seam, as files alone: a benchmark file of the tests, in no benchmark
PPO_BENCH = "tests/perfbench/fixtures/ppo_bench.json"
PPO_CELL = "ppo_tiny.vec8"
# `parametrize("cell,bench_file", ANY_CELLS)`: every cell the any-cell checks of `pb_checks.py` run over
ANY_CELLS = [pytest.param(c, BENCH_FILE, id=c) for c in CELLS] + [pytest.param(PPO_CELL, PPO_BENCH, id=PPO_CELL)]


def mix_files():
    """Every mix file there is: the benchmark's and the tests' fixtures'."""
    import glob

    return sorted(glob.glob(os.path.join(ROOT, "perfbench", "traffic", "*.json"))
                  + glob.glob(os.path.join(ROOT, "tests", "perfbench", "fixtures", "traffic", "*.json")))


def config_files():
    import glob

    return sorted(glob.glob(os.path.join(ROOT, "perfbench", "configs", "*.json"))
                  + glob.glob(os.path.join(ROOT, "tests", "perfbench", "fixtures", "configs", "*.json")))


def run_harness(*args, timeout=900):
    """One `perfbench/run.py` process on the CPU backend; (rc, stdout lines, stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "perfbench", "run.py"), *args],
                          capture_output=True, text=True, env=env, cwd=ROOT, timeout=timeout)
    return proc.returncode, proc.stdout.strip().splitlines(), proc.stderr
