"""What every adapter's taps share while `cli.run` runs, and nothing of any
one algorithm.

The program is driven through its own entry (`sheeprl_tpu.cli.run`). An
adapter (`perfbench/adapters/<name>.py`, named by the configuration's file)
wraps names of one algorithm's module for the length of one run, none of them
replaced, and hands every train call to the `Run` below:

* ``run.before_call()`` before the program's own train call starts: the
  capture starts one call ahead of the window;
* ``run.stamp(g, out)`` when it has returned: the return is stamped with the
  host clock and its G, the window opens and closes on those stamps, the
  capture stops and the guard's wall-clock budget is spent. One method, so
  that every adapter's window is the same window;
* ``run.wrap_guard``: the guard's wall-clock stopper (the object behind
  ``algo.max_wall_time_s``) is told its budget is spent once the window has
  closed, so the loop leaves by its own clean stop, without a checkpoint.

What an adapter keeps for its comparison (host copies of what the first calls
were given and gave back) it hangs on the run under names of its own, and
books the host seconds they cost in ``run.check_s``.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, List, Optional


def flat_names(tree: Any) -> Dict[str, Any]:
    import jax

    def part(p: Any) -> str:
        for attr in ("key", "name", "idx"):
            if hasattr(p, attr):
                return str(getattr(p, attr))
        return str(p)

    return {"/".join(part(p) for p in path): leaf for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


class Run:
    """State of one run, shared by the adapter's wrappers and `run.py`."""

    def __init__(self, seed: int, seconds: float, warmup_calls: int, trace_dir: Optional[str], log: Callable[[str], None]):
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.warmup_calls = max(int(warmup_calls), 1)  # an adapter raises it over the calls its reference follows
        self.trace_dir = trace_dir
        self.log = log
        self.guard: Any = None
        self.cfg: Any = None  # the composed config, as `build_agent` gets it
        self.shapes: Dict[str, Any] = {}
        self.notes: Dict[str, Any] = {}  # what an adapter wants in the window's log line (which ring `auto` chose)
        self.calls_t: List[float] = []  # host clock at each train call's return
        self.calls_g: List[int] = []
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.trace_started = False
        self.check_s = 0.0  # host seconds the adapter's copies cost (inside set-up)

    # -- build_agent ---------------------------------------------------------
    def seeded(self, tree: Any, device: Any) -> Any:
        """`tree` (the abstract weight tree of the program's `build_agent`)
        with every leaf made by the benchmark from the seed, in one jitted call
        on `device`; its names and shapes go to `self.shapes`."""
        import jax
        import numpy as np

        from .reference import make_weights

        flat = flat_names(tree)
        self.shapes = {n: (tuple(x.shape), x.dtype) for n, x in flat.items()}
        made = make_weights(self.seed, self.shapes, device)
        self.log(f"weights from the seed: {len(flat)} leaves, {sum(int(np.prod(s)) for s, _ in self.shapes.values())} values")
        return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(tree), [made[n] for n in flat])

    # -- every train call --------------------------------------------------
    def before_call(self) -> None:
        if self.trace_dir and not self.trace_started and len(self.calls_t) + 1 == self.warmup_calls:
            import jax

            jax.profiler.start_trace(self.trace_dir)
            self.trace_started = True

    def stamp(self, g: int, out: Any) -> None:
        """A train call of `g` gradient steps has returned `out`."""
        import jax

        n = len(self.calls_t)
        now = time.perf_counter()
        self.calls_t.append(now)
        self.calls_g.append(int(g))
        if self.t_open is None:
            if n + 1 >= self.warmup_calls:
                self.t_open = now
                if self.trace_started:
                    with jax.profiler.TraceAnnotation("perfbench.window_open"):
                        pass
                self.log(f"window opens after {n + 1} train calls")
        elif self.t_close is None and now - self.t_open >= self.seconds:
            self.t_close = now
            if self.trace_started:
                with jax.profiler.TraceAnnotation("perfbench.window_close"):
                    pass
            self.log(f"window closed after {now - self.t_open:.3f}s")
            if self.trace_started:
                jax.block_until_ready(out)
                jax.profiler.stop_trace()
                self.trace_started = False
                self.log("trace written")
            if self.guard is not None:
                self.guard.wall.max_s = 1e-9  # budget spent: clean stop at the next step boundary

    # -- RunGuard ----------------------------------------------------------
    def wrap_guard(self, orig: Any) -> Any:
        run = self

        class GuardTap:
            @staticmethod
            def setup(*args: Any, **kwargs: Any):
                run.guard = orig.setup(*args, **kwargs)
                return run.guard

        return GuardTap

    @contextlib.contextmanager
    def patched(self, module: Any, wraps: Dict[str, Callable[[Any], Any]]):
        """`module.<name>` is `wraps[name](the original)` inside the block."""
        saved = {name: getattr(module, name) for name in wraps}
        for name, wrap in wraps.items():
            setattr(module, name, wrap(saved[name]))
        try:
            yield self
        finally:
            for name, orig in saved.items():
                setattr(module, name, orig)
            if self.trace_started:
                import jax

                jax.profiler.stop_trace()
                self.trace_started = False
