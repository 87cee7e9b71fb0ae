"""Rehearsal before any chip time: compile `_gather_batch`, `_scatter_rows` and
`train` for a *described* v5e (no chip attached) at each cell's real sizes, and
print what each needs against the chip's 15.75 GB. Run by hand:

    JAX_PLATFORMS=cpu python3 perfbench/rehearse.py [workload ...]

Nothing runs, so nothing here is a time or a result. Never collected by pytest.
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

HBM_USABLE = 15.75e9
RESERVED = 0.26e9


def main(argv) -> int:
    import gymnasium as gym
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from perfbench import run as prun
    from perfbench import work
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import build_optimizers, make_train_fn
    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.data.device_ring import _gather_batch, _scatter_rows
    from sheeprl_tpu.parallel import Distributed

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def like(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    from perfbench.taps import flat_names

    def items_sds(lead, items):
        return {k: sds(tuple(lead) + shape, jnp.uint8 if np.dtype(d) == np.uint8 else jnp.float32) for k, (shape, d) in items.items()}

    bench = prun.load_json(ROOT, "BENCHMARK.json")
    names = argv or [w["name"] for w in bench["workloads"]]
    for name in names:
        spec = prun.load_cell(name)
        mix, conf = spec["mix"], spec["config"]
        cfg = compose("config", prun.overrides_for(spec, 0, False) + ["algo.world_model.conv_impl=xla"])
        T, B = int(cfg.algo.per_rank_sequence_length), int(cfg.algo.per_rank_batch_size)
        A = int(mix["action"]["n"])
        space = {k: gym.spaces.Box(0, 255, tuple(v["shape"]), np.uint8) for k, v in mix["observation"].items()}
        space["reward"] = gym.spaces.Box(-np.inf, np.inf, (1,), np.float32)
        space = gym.spaces.Dict(space)
        dist = Distributed(devices=1)
        made = {}

        def build(key):
            wm, actor, critic, params = build_agent(dist, cfg, space, [A], False, key)
            made["mods"] = (wm, actor, critic)
            return params

        params = jax.eval_shape(build, jax.random.key(0))
        wm, actor, critic = made["mods"]
        made_tx = {}

        def opt(p):
            txs, states = build_optimizers(cfg, p)
            made_tx["txs"] = txs
            return states

        opt_states = jax.eval_shape(opt, params)
        train = make_train_fn(wm, actor, critic, made_tx["txs"], cfg, False, [A])
        items = work.ring_items(mix, A)
        batch = items_sds((1, T, B), items)
        keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(1), 1))
        out = {"workload": name}
        t0 = time.time()
        compiled = train.lower(like(params), like(opt_states), like(init_moments()), batch, like(keys)).compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        out["xla_cost_analysis_flops"] = float(cost.get("flops", float("nan")))
        out["train"] = {"args_gb": mem.argument_size_in_bytes / 1e9, "temp_gb": mem.temp_size_in_bytes / 1e9,
                        "code_gb": mem.generated_code_size_in_bytes / 1e9, "compile_s": time.time() - t0}
        rows, n_envs = int(cfg.buffer.size), int(cfg.env.num_envs)
        ring = items_sds((rows, n_envs), items)
        mem = _gather_batch.lower(ring, sds((1, T, B), jnp.int32), sds((B,), jnp.int32), ()).compile().memory_analysis()
        out["gather"] = {"args_gb": mem.argument_size_in_bytes / 1e9, "temp_gb": mem.temp_size_in_bytes / 1e9}
        n = 8 if n_envs == 1 else 72
        mem = _scatter_rows.lower(ring, items_sds((n,), items), sds((n,), jnp.int32), sds((n,), jnp.int32)).compile().memory_analysis()
        out["scatter"] = {"args_gb": mem.argument_size_in_bytes / 1e9, "temp_gb": mem.temp_size_in_bytes / 1e9,
                          "alias_gb": mem.alias_size_in_bytes / 1e9}
        shapes = {k: (x.shape, x.dtype) for k, x in flat_names(params).items()}
        kept = work.kept_bytes(shapes, mix, rows, A)
        out["kept_gb"] = {k: v / 1e9 for k, v in kept.items()}
        out["kept_gib"] = kept["total"] / 2**30
        ring_gb = kept["ring"] / 1e9
        worst = (kept["params"] + kept["adam"]) / 1e9 + out["train"]["temp_gb"] + 3 * ring_gb + RESERVED / 1e9
        out["worst_case_gb"] = worst
        out["hbm_gb"] = HBM_USABLE / 1e9
        out["fits"] = worst < HBM_USABLE / 1e9
        out["flops_per_grad_step"] = work.train_step_flops(shapes, T, B, int(cfg.algo.horizon))
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
