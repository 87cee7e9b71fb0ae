"""Promote a bench record to artifacts/BENCH_TPU_BEST.json if it is the best
real-accelerator run so far (highest vs_baseline, platform not cpu-*).

Usage: python scripts/keep_best_bench.py <new_record.json>
The input file holds bench.py stdout (one JSON record per line; last line is
the headline). Run it after a bench run to keep the best accelerator
record so far on disk.
"""
from __future__ import annotations

import json
import os
import sys

BEST = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "artifacts", "BENCH_TPU_BEST.json")


def last_record(path: str) -> dict | None:
    try:
        lines = [ln for ln in open(path).read().strip().splitlines() if ln.strip()]
        return json.loads(lines[-1]) if lines else None
    except (OSError, json.JSONDecodeError):
        return None


def main() -> None:
    rec = last_record(sys.argv[1])
    if rec is None:
        print(f"[keep_best] no parseable record in {sys.argv[1]}")
        return
    plat = str(rec.get("platform", ""))
    if not plat or plat.startswith("cpu"):
        print(f"[keep_best] platform={plat!r} — not an accelerator record, skipping")
        return
    # only healthy END-TO-END headlines compete: a promoted compute-only
    # record (e2e leg failed) uses a different baseline, so its vs_baseline
    # is not comparable — keeping it would lock out every later real run
    if "e2e_error" in rec or "error" in rec or rec.get("unit") != "env steps/sec":
        print(f"[keep_best] not a healthy e2e headline (unit={rec.get('unit')!r}, "
              f"error={rec.get('error') or rec.get('e2e_error')!r}), skipping")
        return
    cur = last_record(BEST)
    if cur is not None and cur.get("vs_baseline", 0) >= rec.get("vs_baseline", 0):
        print(f"[keep_best] existing best {cur.get('vs_baseline')} >= {rec.get('vs_baseline')}")
        return
    rec["source_file"] = os.path.basename(sys.argv[1])
    with open(BEST, "w") as f:
        json.dump(rec, f)
        f.write("\n")
    print(f"[keep_best] new best: vs_baseline={rec.get('vs_baseline')} platform={plat}")


if __name__ == "__main__":
    main()
