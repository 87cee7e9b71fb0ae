"""What the harness hangs on the program while `cli.run` runs, and nothing else.

The program is driven through its own entry (`sheeprl_tpu.cli.run`). Four
names in `algos/dreamer_v3/dreamer_v3.py` are wrapped for the length of one
run, none of them replaced:

* ``build_agent``: the weights it returns are overwritten, leaf by leaf and in
  place of the same shape, type and placement, by the benchmark's own seeded
  weights (`reference.make_weights`): the reference then never takes a weight
  the program made;
* ``make_train_fn``: the returned ``train`` is called as the loop calls it.
  The wrapper stamps every call's return with the host clock and its G, opens
  and closes the measured window on those stamps, and during set-up keeps
  host copies of what the first calls were given and gave back;
* ``RunGuard``: the guard's wall-clock stopper (the object behind
  ``algo.max_wall_time_s``) is told its budget is spent once the window has
  closed, so the loop leaves by its own clean stop, without a checkpoint;
* ``make_sequential_prefetcher``: only looked at, to report which ring the
  ``auto`` option resolved to.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

CHECK_STEPS = 3  # the reference follows the first three gradient steps


def flat_names(tree: Any) -> Dict[str, Any]:
    import jax

    def part(p: Any) -> str:
        for attr in ("key", "name", "idx"):
            if hasattr(p, attr):
                return str(getattr(p, attr))
        return str(p)

    return {"/".join(part(p) for p in path): leaf for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


class Run:
    """State of one run, shared by the wrappers and `run.py`."""

    def __init__(self, seed: int, seconds: float, warmup_calls: int, trace_dir: Optional[str], log: Callable[[str], None]):
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.warmup_calls = max(int(warmup_calls), CHECK_STEPS + 1)
        self.trace_dir = trace_dir
        self.log = log
        self.guard: Any = None
        self.prefetcher: Any = None
        self.cfg: Any = None  # the composed config, as `build_agent` gets it
        self.shapes: Dict[str, Any] = {}
        self.calls_t: List[float] = []  # host clock at each train call's return
        self.calls_g: List[int] = []
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.trace_started = False
        # host copies for the comparison: per check step the batch and key
        # the call got and the losses it returned; mu after step 1; the
        # parameters after step CHECK_STEPS
        self.batches: List[Dict[str, np.ndarray]] = []
        self.keys: List[np.ndarray] = []
        self.losses: List[Dict[str, float]] = []
        self.mu1: Optional[Dict[str, np.ndarray]] = None
        self.params_after: Optional[Dict[str, np.ndarray]] = None
        self.check_s = 0.0  # host seconds the copies cost (inside set-up)

    # -- build_agent --------------------------------------------------------
    def wrap_build_agent(self, orig: Callable) -> Callable:
        def build_agent(*args: Any, **kwargs: Any):
            import jax

            from . import reference

            # the tree's names and shapes, without running the program's own
            # initializers; the seeded weights then go in through the
            # program's own `state` argument (its resume path)
            dist, cfg, obs_space, actions_dim, is_continuous, key = args[:6]
            self.cfg = cfg
            made: Dict[str, Any] = {}

            def abstract(k):
                made["out"] = orig(dist, cfg, obs_space, actions_dim, is_continuous, k)
                return made["out"][3]

            tree = jax.eval_shape(abstract, key)
            flat = flat_names(tree)
            self.shapes = {n: (tuple(x.shape), x.dtype) for n, x in flat.items()}
            seeded = reference.make_weights(self.seed, self.shapes, dist.local_device)
            treedef = jax.tree_util.tree_structure(tree)
            params = jax.tree_util.tree_unflatten(treedef, [seeded[n] for n in flat])
            self.log(f"weights from the seed: {len(flat)} leaves, {sum(int(np.prod(s)) for s, _ in self.shapes.values())} values")
            return orig(dist, cfg, obs_space, actions_dim, is_continuous, key, params)

        return build_agent

    # -- make_train_fn --------------------------------------------------------
    def wrap_make_train_fn(self, orig: Callable) -> Callable:
        return lambda *args, **kwargs: self._wrap_train(orig(*args, **kwargs))

    def _wrap_train(self, train: Callable) -> Callable:
        def timed_train(params, opt_states, moments, batches, keys):
            import jax

            n = len(self.calls_t)
            g = int(keys.shape[0])
            checking = n < CHECK_STEPS
            if checking:
                t0 = time.perf_counter()
                if g != 1:
                    raise RuntimeError(f"the first train calls must take one gradient step each, got G={g}")
                self.batches.append({k: np.asarray(v)[0] for k, v in batches.items()})
                self.keys.append(np.asarray(jax.random.key_data(keys))[0])
                self.check_s += time.perf_counter() - t0
            if self.trace_dir and not self.trace_started and n + 1 == self.warmup_calls:
                jax.profiler.start_trace(self.trace_dir)
                self.trace_started = True
            out = train(params, opt_states, moments, batches, keys)
            if checking:
                t0 = time.perf_counter()
                new_params, new_opt, _, metrics = out
                self.losses.append({
                    "wm": float(np.asarray(metrics["Loss/world_model_loss"])[0]),
                    "actor": float(np.asarray(metrics["Loss/policy_loss"])[0]),
                    "critic": float(np.asarray(metrics["Loss/value_loss"])[0]),
                })
                if n == 0:
                    self.mu1 = {k: np.asarray(v) for k, v in flat_names(new_opt).items() if "/mu/" in k}
                if n == CHECK_STEPS - 1:
                    self.params_after = {k: np.asarray(v) for k, v in flat_names(new_params).items()}
                self.check_s += time.perf_counter() - t0
            now = time.perf_counter()
            self.calls_t.append(now)
            self.calls_g.append(g)
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
                    jax.block_until_ready(out[0])
                    jax.profiler.stop_trace()
                    self.trace_started = False
                    self.log("trace written")
                if self.guard is not None:
                    self.guard.wall.max_s = 1e-9  # budget spent: clean stop at the next step boundary
            return out

        return timed_train

    # -- RunGuard / prefetcher ------------------------------------------------
    def wrap_guard(self, orig: Any) -> Any:
        run = self

        class GuardTap:
            @staticmethod
            def setup(*args: Any, **kwargs: Any):
                run.guard = orig.setup(*args, **kwargs)
                return run.guard

        return GuardTap

    def wrap_prefetcher(self, orig: Callable) -> Callable:
        def make_sequential_prefetcher(*args: Any, **kwargs: Any):
            self.prefetcher = orig(*args, **kwargs)
            return self.prefetcher

        return make_sequential_prefetcher

    @contextlib.contextmanager
    def installed(self):
        from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3

        wraps = {
            "build_agent": self.wrap_build_agent,
            "make_train_fn": self.wrap_make_train_fn,
            "RunGuard": self.wrap_guard,
            "make_sequential_prefetcher": self.wrap_prefetcher,
        }
        saved = {name: getattr(dv3, name) for name in wraps}
        for name, wrap in wraps.items():
            setattr(dv3, name, wrap(saved[name]))
        try:
            yield self
        finally:
            for name, orig in saved.items():
                setattr(dv3, name, orig)
            if self.trace_started:
                import jax

                jax.profiler.stop_trace()
                self.trace_started = False
