"""Read what `many.py` kept: per end-to-end metric the median and the quartile
spread (statistics.quantiles, n=4, as a share of the median) of each set, and
per compared number the largest reading over all runs.

    python3 perfbench/spread.py <dir> [<dir> ...]
"""
from __future__ import annotations

import glob
import json
import os
import statistics
import sys


def load(d):
    runs = []
    for path in sorted(glob.glob(os.path.join(d, "*.out"))):
        with open(path) as f:
            lines = f.read().strip().splitlines()
        if not lines:
            continue
        try:
            rec = json.loads(lines[-1])
        except ValueError:
            continue
        reads = {}
        with open(path[:-4] + ".err") as f:
            for ln in f:
                if ln.startswith(("[read]", "[compared]")):
                    parts = ln.split()
                    reads[parts[1]] = float(parts[3])
        runs.append((os.path.basename(path), rec, reads))
    return runs


def main(argv) -> int:
    for d in argv:
        runs = load(d)
        by_cell = {}
        for name, rec, reads in runs:
            cell = name.split("_t")[0]
            by_cell.setdefault(cell, []).append((name, rec, reads))
        for cell, rs in by_cell.items():
            print(f"== {d} {cell}: {len(rs)} runs, correct {[r['correct'] for _, r, _ in rs]}")
            metrics = {}
            for _, rec, _ in rs:
                for k, v in rec["metrics"].items():
                    metrics.setdefault(k, []).append(v["value"])
            for k, vals in metrics.items():
                if len(vals) >= 2:
                    q = statistics.quantiles(vals, n=4)
                    med = statistics.median(vals)
                    spread = f"{(q[2] - q[0]) / med:.4%}" if med else "-"  # a metric that reads 0 in every run has none
                    print(f"  {k}: median {med:.6g} spread {spread} min {min(vals):.6g} max {max(vals):.6g} n={len(vals)}")
                else:
                    print(f"  {k}: {vals}")
            worst = {}
            for _, _, reads in rs:
                for k, v in reads.items():
                    worst.setdefault(k, []).append(v)
            for k, vals in worst.items():
                print(f"  [max] {k}: {max(vals):.4g}   all: {' '.join(f'{v:.3g}' for v in vals)}")
            peaks = [rec["device"]["memory_peak_bytes"] for _, rec, _ in rs]
            print(f"  memory_peak_bytes max {max(peaks)} = {max(peaks) / 2**30:.3f} GiB")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
