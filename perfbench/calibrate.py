"""The readings a limit is set from, that no benchmark run makes: the control
and the planted faults of the cell's family (its adapter's `calibrate`), each
put in the program's place and compared with the plain reference by the same
numbers as a run, at the cell's own sizes, on rows from the cell's own
generator. Run by hand on the chip:

    python3 perfbench/calibrate.py <workload> <out.json> <seed> [<seed> ...] [--sides a,b] [--rehearse-cpu]

One process reads all seeds (each side's program compiles once). `--sides`
names the sides to read (the adapter's docstring lists them); all of them
without it. The program's own lower-precision path is read through the
harness instead: `run.py --control bf16-mixed`.
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def main(argv) -> int:
    rehearse = "--rehearse-cpu" in argv
    argv = [a for a in argv if a != "--rehearse-cpu"]
    only = None
    if "--sides" in argv:
        i = argv.index("--sides")
        only = argv[i + 1].split(",")
        argv = argv[:i] + argv[i + 2:]
    workload, out_path, *seeds = argv
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".xla_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from perfbench import adapters, run as prun

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    dev = jax.devices()[0]
    print(f"[calibrate] platform={dev.platform} device_kind={dev.device_kind!r}", file=sys.stderr)
    if dev.platform != "tpu" and not rehearse:
        print("[calibrate] needs a TPU", file=sys.stderr)
        return 3
    spec = prun.load_cell(workload)
    adapter = adapters.load(spec["config"]["adapter"])
    if not hasattr(adapter, "calibrate"):
        print(f"[calibrate] adapter {spec['config']['adapter']!r} has no `calibrate`", file=sys.stderr)
        return 2
    results = []
    for rec in adapter.calibrate(spec, [int(s) for s in seeds], only, rehearse):
        print(json.dumps(rec), flush=True)
        results.append(rec)
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
