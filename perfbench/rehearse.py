"""Rehearsal before any chip time: compile each cell's device programs for a
*described* v5e (no chip attached) at the cell's real sizes, through its
adapter's `rehearse`, and print what each needs against the chip's memory,
and beside `fits` what the cell KEEPS across calls (the adapter's
`kept_bytes`) against the driver's floor for a cell's size: a quarter of the
chip's memory, or an eighth with the chip busy at least 75 % of the traced
window. Run by hand:

    JAX_PLATFORMS=cpu python3 perfbench/rehearse.py [--benchmark <file>] [workload ...]

Nothing runs, so nothing here is a time or a result. Never collected by pytest.
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

HBM_USABLE = 15.75e9  # of the chip's 16 GB, what a program may take
FLOOR_SHARE, FLOOR_SHARE_BUSY = 0.25, 0.125  # of one chip's memory; the second with busy_s >= 75 % of window_s


def main(argv) -> int:
    import jax
    from jax.experimental import topologies

    from perfbench import adapters, peaks
    from perfbench import run as prun

    benchmark = "BENCHMARK.json"
    if "--benchmark" in argv:
        i = argv.index("--benchmark")
        benchmark, argv = argv[i + 1], argv[:i] + argv[i + 2:]
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip_gib = peaks.lookup("TPU v5 lite")["hbm_bytes"] / 1e9  # the driver reckons 16 GB as 16 GiB
    names = argv or [w["name"] for w in prun.load_json(ROOT, benchmark)["workloads"]]
    for name in names:
        spec = prun.load_cell(name, benchmark)
        adapter = adapters.load(spec["config"]["adapter"])
        out = {"workload": name, **adapter.rehearse(spec, topo), "hbm_gb": HBM_USABLE / 1e9}
        out["fits"] = out["worst_case_gb"] < out["hbm_gb"]
        _, shapes = adapter.program_shapes(spec)
        kept = adapter.kept_bytes(shapes, spec)
        out["kept_gb"] = {k: v / 1e9 for k, v in kept.items()}
        out["kept_gib"] = kept["total"] / 2**30
        out["floor_gib"] = FLOOR_SHARE * chip_gib
        out["floor_gib_if_busy_75pct"] = FLOOR_SHARE_BUSY * chip_gib
        out["over_floor"] = out["kept_gib"] >= out["floor_gib"]
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
