"""Run one cell several times, one process each, and keep what each printed:

    python3 perfbench/many.py <out_dir> <workload> <seconds> <trace> <seed> [<seed> ...] [-- extra run.py args]

The parent never touches JAX (one process uses the chip at a time). Writes
<out_dir>/<workload>_t<trace>_<seed>.out/.err and prints each run's last line.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time


def main(argv) -> int:
    extra = []
    if "--" in argv:
        i = argv.index("--")
        argv, extra = argv[:i], argv[i + 1:]
    out_dir, workload, seconds, trace, *seeds = argv
    os.makedirs(out_dir, exist_ok=True)
    here = os.path.dirname(os.path.abspath(__file__))
    rc = 0
    for seed in seeds:
        t0 = time.time()
        base = os.path.join(out_dir, f"{workload}_t{trace}_{seed}")
        cmd = [sys.executable, os.path.join(here, "run.py"), "--workload", workload, "--seed", seed,
               "--seconds", seconds, "--trace", trace, "--keep", out_dir] + extra
        with open(base + ".out", "w") as so, open(base + ".err", "w") as se:
            code = subprocess.run(cmd, stdout=so, stderr=se).returncode
        with open(base + ".out") as f:
            lines = f.read().strip().splitlines()
        with open(base + ".err") as f:
            marks = [ln for ln in f.read().splitlines() if ln.startswith(("[perfbench]", "[compared]", "[read]", "Traceback", "RuntimeError", "ValueError", "jax", "XlaRuntimeError"))]
        print(f"== {workload} seed={seed} trace={trace} rc={code} wall={time.time() - t0:.1f}s")
        print("\n".join(marks[-60:]))
        print(lines[-1][:3000] if lines else "(no result line)")
        sys.stdout.flush()
        rc = rc or code
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
