"""The program's own event stream of the run that was just measured
(`telemetry.jsonl` under the run's log directory, which `run.py` keeps in the
same temporary directory as the capture until the readers are done): for the
per-layer metrics whose source is a counter the program emits as an event."""
from __future__ import annotations

import glob
import json
import os
from typing import Any, Dict, List


def events(ctx: Dict[str, Any], name: str) -> List[Dict[str, Any]]:
    """Every event of that name the run wrote, in order; [] where there is no stream or no such event."""
    if not ctx.get("trace_dir"):
        return []
    out: List[Dict[str, Any]] = []
    for path in sorted(glob.glob(os.path.join(os.path.dirname(ctx["trace_dir"]), "logs", "runs", "**", "telemetry.jsonl*"), recursive=True)):
        with open(path) as f:
            for line in f:
                if f'"{name}"' in line:
                    rec = json.loads(line)
                    if rec.get("event") == name:
                        out.append(rec)
    return out
