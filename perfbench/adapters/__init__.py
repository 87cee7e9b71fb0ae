"""One file per algorithm or model family, found by the name a configuration's
file gives under `"adapter"`. PERF.md section 4 has what each name is for."""
from __future__ import annotations

import importlib
from types import ModuleType

CONTRACT = ("installed", "program_shapes", "seed_weights", "decide", "step_flops", "kept_bytes", "step_programs", "step_parts",
            "rehearsal_overrides", "rehearse", "faults", "widths_of", "compared_numbers", "fault_kinds")


def load(name: str) -> ModuleType:
    """`perfbench/adapters/<name>.py`, refused where it lacks part of the contract."""
    module = importlib.import_module(f"{__name__}.{name}")
    missing = [attr for attr in CONTRACT if not hasattr(module, attr)]
    if missing:
        raise AttributeError(f"adapter {name!r} lacks {missing}")
    return module
