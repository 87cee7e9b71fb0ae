"""The readings a limit is set from, that no benchmark run makes: the control
(the reference with every matmul and conv operand rounded to float8_e4m3fn,
the precision below the bfloat16 operands the configuration states) and the
planted faults (half of the batch left out; the state left unchanged), each
put in the program's place and compared with the plain reference by the same
numbers as a run, at the cell's own sizes, on rows from the cell's own
generator. Run by hand on the chip:

    python3 perfbench/calibrate.py <workload> <out.json> <seed> [<seed> ...] [--sides a,b] [--rehearse-cpu]

One process reads all seeds (each side's program compiles once). `--sides`
names the sides to read, of control_fp8, fault_half_batch, fault_unchanged,
fault_unchanged_actor and bf16_operands; all of them without it. The
program's own lower-precision path is read through the harness instead:
`run.py --control bf16-mixed`.
"""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def batches_from_generator(mix_name: str, mix, seed: int, T: int, B: int, steps: int, n_batches: int):
    """[T, B] batches as the loop would store them, from the generator alone."""
    import numpy as np

    from perfbench.envs import SyntheticEnv, _rng

    n_envs = int(mix["num_envs"])
    A = int(mix["action"]["n"])
    rows = []
    for e in range(n_envs):
        env = SyntheticEnv(mix_name, bench_seed=seed, rank=e)
        rng = _rng(seed, e, 7)
        obs, _ = env.reset()
        out = []

        def row(obs, n, action):
            a = np.zeros((A,), np.float32)
            if action is not None:
                a[action] = 1.0
            final = env.log_final[n]
            return {
                "rgb": obs["rgb"], "reward": np.array([env.log_reward[n]], np.float32), "actions": a,
                "rewards": np.array([env.log_reward[n]], np.float32),
                "terminated": np.array([float(env.log_terminated[n] and final)], np.float32),
                "truncated": np.array([float(env.log_truncated[n] and final)], np.float32),
                "is_first": np.array([float(env.log_first[n])], np.float32),
            }

        for _ in range(steps):
            a = int(rng.integers(0, A))
            n = env.n - 1
            prev_obs = obs
            obs, r, term, trunc, _ = env.step(a)
            out.append(row(prev_obs, n, a))
            if term or trunc:
                out.append(row(obs, env.n - 1, None))
                obs, _ = env.reset()
        rows.append(out)
    rng = _rng(seed, 99)
    batches = []
    for _ in range(n_batches):
        cols = []
        for b in range(B):
            e = int(rng.integers(0, n_envs))
            s = int(rng.integers(0, len(rows[e]) - T))
            cols.append(rows[e][s:s + T])
        batches.append({k: np.stack([np.stack([cols[b][t][k] for b in range(B)]) for t in range(T)]) for k in cols[0][0]})
    return batches


def main(argv) -> int:
    rehearse = "--rehearse-cpu" in argv
    argv = [a for a in argv if a != "--rehearse-cpu"]
    only = None
    if "--sides" in argv:
        i = argv.index("--sides")
        only = argv[i + 1].split(",")
        argv = argv[:i] + argv[i + 2:]
    workload, out_path, *seeds = argv
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".xla_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import check, run as prun
    from perfbench.taps import CHECK_STEPS

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    dev = jax.devices()[0]
    print(f"[calibrate] platform={dev.platform} device_kind={dev.device_kind!r}", file=sys.stderr)
    if dev.platform != "tpu" and not rehearse:
        print("[calibrate] needs a TPU", file=sys.stderr)
        return 3
    spec = prun.load_cell(workload)
    cfg, shapes = check.program_shapes(spec, rehearse)
    sz = check.sizes_for(cfg, spec["mix"])
    T, B = int(cfg.algo.per_rank_sequence_length), int(cfg.algo.per_rank_batch_size)
    # bf16_operands is no control: it is what a TPU's default precision does to `32-true`, read
    # to show how far that alone moves each number from pure float32
    sides = {
        "control_fp8": {"od": jnp.float8_e4m3fn}, "fault_half_batch": {"faults": ("half_batch",)},
        "fault_unchanged": {"faults": ("unchanged",)}, "fault_unchanged_actor": {"faults": ("unchanged_actor",)},
        "bf16_operands": {"od": jnp.bfloat16},
    }
    sides = {k: v for k, v in sides.items() if only is None or k in only}
    results = []
    for seed in (int(s) for s in seeds):
        t0 = time.time()
        batches = batches_from_generator(spec["cell"]["traffic"], spec["mix"], seed, T, B, 200 if rehearse else 1100, CHECK_STEPS)
        keys = [np.asarray(jax.random.key_data(jax.random.split(jax.random.key(seed % 2147483647 + i), 1)))[0] for i in range(CHECK_STEPS)]
        ref = check.reference_side(seed, shapes, batches, keys, sz)
        rec = {"seed": seed}
        for name, kw in sides.items():
            side = check.reference_side(seed, shapes, batches, keys, sz, **kw)
            rec[name], _ = check.compare_sides(side, ref)
        rec["seconds"] = time.time() - t0
        print(json.dumps(rec), flush=True)
        results.append(rec)
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
