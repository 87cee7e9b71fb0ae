"""Shared by the perfbench tests: the cells by name, and one harness process on the CPU."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


CELLS = [w["name"] for w in bench()["workloads"]]
# a second algorithm through the harness's seam, as files alone: a benchmark file of the tests, in no benchmark
PPO_BENCH = "tests/perfbench/fixtures/ppo_bench.json"
PPO_CELL = "ppo_tiny.vec8"


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
