"""Overrides every cell gets. The ones only the CPU rehearsal adds are its
algorithm's: `rehearsal_overrides` of the configuration's adapter."""

# what keeps a run short and the checkout clean; none of it is in the steady loop
COMMON_OVERRIDES = [
    "buffer.checkpoint=False",
    "checkpoint.save_last=False",
    "checkpoint.every=1000000000",
    "algo.run_test=False",
    "model_manager.disabled=True",
    "algo.total_steps=1000000000",
    "algo.max_wall_time_s=330",  # a net under the run; the window closes it far sooner
]


# `tests/test_train_scopes.py` (outside the benchmark's own directories, so no benchmark PR may edit it) still imports
# DreamerV3's rehearsal overrides from here, and its step's parts through `span_reduce.PARTS` and
# `span_reduce.part_of(op_name)`; both live with their adapter
_LAZY = {"REHEARSAL_OVERRIDES": "rehearsal_overrides", "STEP_PARTS": "step_parts"}


def __getattr__(name: str):
    if name in _LAZY:
        from .adapters import dreamer_v3

        return getattr(dreamer_v3, _LAZY[name])
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
