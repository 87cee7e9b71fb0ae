"""One run of one cell of BENCHMARK.json:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Drives `sheeprl_tpu.cli.run` in this process with the cell's configuration
and traffic mix, measures a window on the benchmark's own clock, compares what
the timed path produced with the plain reference, and prints one JSON line.
Everything that belongs to one configuration, one mix, one per-layer metric or
one algorithm is in a file of its own, found by name: the configuration's file
by the benchmark file, the mix, the limits and the adapter by the
configuration (`<home>/traffic/<mix>.json`, `<home>/limits/<config>.json`,
`perfbench/adapters/<adapter>.py`; `<home>` is the directory above the
configuration's), the generator by the mix. `--benchmark` names another
benchmark file than the root's, for the tests.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import adapters  # noqa: E402
from perfbench.overrides import COMMON_OVERRIDES  # noqa: E402


def log(msg: str) -> None:
    print(f"[perfbench] +{time.perf_counter() - T_START:.1f}s {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, benchmark: str = "BENCHMARK.json") -> Dict[str, Any]:
    bench = load_json(ROOT, benchmark)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in {benchmark}")
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    home = os.path.dirname(os.path.dirname(os.path.join(ROOT, config["file"])))
    mix_file = os.path.join(home, "traffic", f"{cell['traffic']}.json")
    return {
        "bench": bench,
        "cell": cell,
        "config": load_json(ROOT, config["file"]),
        "mix": load_json(mix_file),
        # how the generator is told its mix: by name under perfbench/, by path from the root elsewhere
        "mix_ref": cell["traffic"] if home == HERE else os.path.relpath(mix_file, ROOT),
        "limits_file": os.path.join(home, "limits", f"{config['name']}.json"),
    }


def overrides_for(spec: Dict[str, Any], seed: int, rehearse: bool) -> List[str]:
    mix = spec["cell"]["traffic"]
    wrapper = "{" + f"_target_: {spec['mix']['generator']}, mix: {spec['mix_ref']}, seed: 0, rank: 0, bench_seed: {int(seed)}" + "}"
    out = list(spec["config"]["overrides"]) + list(spec["mix"]["overrides"]) + COMMON_OVERRIDES
    out += [f"env.wrapper={wrapper}", f"env.id=perfbench_{mix}", f"seed={int(seed) % 2147483647}"]
    if rehearse:
        out += adapters.load(spec["config"]["adapter"]).rehearsal_overrides
    return out


def metric_reader(name: str):
    """perfbench/metrics/<name>.py: `read(ctx) -> number or None`."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("perfbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reported_by(entries: List[Dict[str, Any]], workload: str) -> List[Dict[str, Any]]:
    """The metrics of one list of the benchmark file that a cell reports: those that list it under `workloads`, and
    those that have no such key (reported by every cell). Holds for `end_to_end` as for `per_layer`."""
    return [m for m in entries if not m.get("workloads") or workload in m["workloads"]]


def window_numbers(run, envs: Dict[int, Any]) -> Dict[str, Any]:
    import numpy as np

    t0, t1 = run.t_open, run.t_close
    length = t1 - t0
    calls_t = np.asarray(run.calls_t)
    calls_g = np.asarray(run.calls_g)
    inside = (calls_t > t0) & (calls_t <= t1)
    env_steps = 0
    for env in envs.values():
        te = np.asarray(env.t_enter)
        env_steps += int(np.sum((te >= t0) & (te <= t1)))
    e0 = np.asarray(envs[0].t_enter)
    e0 = e0[(e0 >= t0) & (e0 <= t1)]
    gaps = np.diff(e0) * 1e3
    between = np.diff(calls_t[inside]) if inside.sum() > 1 else np.zeros(1)
    return {
        # a stall shows here: the longest waits between two train calls, beside the usual one
        "call_gap_s": {"median": float(np.median(between)), "longest": [float(x) for x in np.sort(between)[-3:][::-1]]},
        "seconds": length,
        "t_open": t0,
        "t_close": t1,
        "env_steps": env_steps,
        "grad_steps": int(calls_g[inside].sum()),
        "train_calls": int(inside.sum()),
        "gaps_ms": gaps,
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--benchmark", default="BENCHMARK.json", help="the benchmark file, from the root of the checkout")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny widths on whatever backend there is; prints no device metric")
    ap.add_argument("--fault", default="", help="tests only: break the timed path through the adapter's `faults` (see tests/perfbench)")
    ap.add_argument("--control", default="", help="calibration only: run the program at this `fabric.precision` (its own lower-"
                    "precision path) in the configuration's place; PERF.md has what it then reads")
    ap.add_argument("--keep", default="", help="write the compared numbers and their detail into this directory")
    ap.add_argument("--keep-trace", type=int, default=0, help="with --keep: copy the raw .xplane.pb there too")
    args = ap.parse_args(argv)
    if args.keep:
        args.keep = os.path.abspath(args.keep)

    spec = load_cell(args.workload, args.benchmark)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".xla_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    chips = int(spec["cell"]["chips"])
    log(f"platform={dev.platform} device_kind={dev.device_kind!r} count={len(devices)} workload={args.workload} seed={args.seed}")
    if not args.rehearse_cpu and (dev.platform != "tpu" or len(devices) < chips):
        print(f"[perfbench] needs {chips} TPU chip(s), found {len(devices)} x {dev.platform}: no result", file=sys.stderr)
        return 3

    from perfbench import check, envs, peaks, taps, work
    from sheeprl_tpu.cli import run as cli_run

    adapter = adapters.load(spec["config"]["adapter"])

    if not args.rehearse_cpu:
        peaks.lookup(dev.device_kind)  # an unknown device kind is an error before any work

    tmp_parent = os.environ.get("TMPDIR") or tempfile.gettempdir()
    tmp = tempfile.mkdtemp(prefix="perfbench_", dir=tmp_parent)
    trace_dir = os.path.join(tmp, "trace") if args.trace else None
    mix = spec["mix"]
    seconds = min(args.seconds, float(mix.get("trace_seconds", args.seconds))) if args.trace else args.seconds
    run = taps.Run(args.seed, seconds, int(mix["warmup_train_calls"]), trace_dir, log)
    overrides = overrides_for(spec, args.seed, args.rehearse_cpu)
    if args.control:
        overrides.append(f"fabric.precision={args.control}")
    envs.reset_registry()
    cwd = os.getcwd()
    os.chdir(tmp)
    try:
        with contextlib.ExitStack() as stack:
            if args.fault:  # planted first, so that it lies beneath the harness's own wrappers
                stack.enter_context(adapter.faults(args.fault))
            stack.enter_context(adapter.installed(run))
            stack.enter_context(contextlib.redirect_stdout(sys.stderr))
            cli_run(overrides)
        log("run returned")
        if run.t_close is None:
            raise RuntimeError("the run ended before the window closed")
        # the fullest of the chips the cell asks for: what the driver's floor reads
        peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices[:chips])
        live_envs = dict(envs.REGISTRY)
        win = window_numbers(run, live_envs)
        num = {
            "window_s": win["seconds"], "env_steps": win["env_steps"], "grad_steps": win["grad_steps"],
            "train_calls": win["train_calls"], "gaps": int(len(win["gaps_ms"])), **run.notes,
            "call_gap_s": win["call_gap_s"],
            "setup_copies_s": run.check_s,
        }
        log(f"window {json.dumps(num)}")

        # the program's state goes before the reference comes
        run.guard = None
        gc.collect()

        metrics: Dict[str, Dict[str, Any]] = {}
        device: Dict[str, Any] = {
            "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
            "memory_peak_bytes": peak_bytes,
        }
        breakdown = None
        bench = spec["bench"]
        units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
        if not args.trace:
            e2e = {
                "env_steps_per_s": win["env_steps"] / win["seconds"],
                "grad_steps_per_s": win["grad_steps"] / win["seconds"],
                "step_gap_p95_ms": float(np.percentile(win["gaps_ms"], 95)) if len(win["gaps_ms"]) else None,
                "setup_s": run.t_open - T_START,
            }
            for m in reported_by(bench["end_to_end"], args.workload):
                if e2e.get(m["name"]) is not None:
                    metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
        else:
            from perfbench import span_reduce, trace_reduce

            t0 = time.perf_counter()
            planes = trace_reduce.read_dir(trace_dir)  # the one parse: both reducers read it
            reduced = trace_reduce.reduce_events(planes)
            capture = span_reduce.Capture(planes, adapter.step_programs, adapter.step_parts)
            log(f"trace reduced in {time.perf_counter() - t0:.1f}s: {reduced['n_device_events']} device events")
            if args.keep and args.keep_trace:
                os.makedirs(args.keep, exist_ok=True)
                for f in trace_reduce.find_xplanes(trace_dir):
                    shutil.copy(f, os.path.join(args.keep, f"{args.workload}_{args.seed}.xplane.pb"))
            ctx = {
                "trace": reduced, "window": win, "envs": live_envs, "device_kind": dev.device_kind,
                "peak_bytes": peak_bytes, "spec": spec, "work": work, "peaks": peaks,
                "shapes": run.shapes, "rehearse": args.rehearse_cpu, "adapter": adapter,
                "trace_dir": trace_dir, "capture": capture,
            }
            for m in reported_by(bench["per_layer"], args.workload):
                value = metric_reader(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value), "unit": units[m["name"]]}
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["top_ops"][:10], "idle_gaps": reduced["idle_gaps"][:10]}
        if args.rehearse_cpu:
            # a CPU run never carries a device metric under a device metric's name
            metrics = {k: v for k, v in metrics.items() if k in ("setup_s",)}
            device.pop("busy_s", None)
            device.pop("window_s", None)
            breakdown = None

        t0 = time.perf_counter()
        compared, detail = check.decide(run, live_envs, spec, adapter)
        log(f"compared in {time.perf_counter() - t0:.1f}s")
        correct = all(c["ok"] for c in compared.values())
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            with open(os.path.join(args.keep, f"{args.workload}_{args.seed}_t{args.trace}.json"), "w") as f:
                json.dump({"compared": compared, "detail": detail, "window": num, "metrics": metrics, "device": device}, f)
    finally:
        os.chdir(cwd)
        shutil.rmtree(tmp, ignore_errors=True)

    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": int(win["env_steps"]),
        "failed": 0,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {k: {"value": c["value"], "limit": c["limit"]} for k, c in compared.items()}
    for k, v in detail.get("not_compared", {}).items():
        print(f"[read] {k} = {v} (no limit: not compared)", file=sys.stderr)
    for k, c in compared.items():
        print(f"[compared] {k} = {c['value']} (limit {c['limit']}){'' if c['ok'] else '  <-- FAILS'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
