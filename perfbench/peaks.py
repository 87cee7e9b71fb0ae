"""The chip's published peaks, keyed by `device_kind` (perfbench/peaks.json).
A device that is not in the table is an error, never a default."""
from __future__ import annotations

import json
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))


def table() -> Dict[str, Dict[str, float]]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        return json.load(f)["by_device_kind"]


def lookup(device_kind: str) -> Dict[str, float]:
    peaks = table()
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in perfbench/peaks.json (has {sorted(peaks)})")
    return peaks[device_kind]
