"""Device-mesh launcher — the TPU-native replacement for Lightning Fabric.

The reference wraps torch.distributed in Fabric (reference
configs/fabric/default.yaml, cli.py:149-199): `launch` spawns one process per
device, `setup_module` wraps modules in DDP, `backward` all-reduces grads over
NCCL/Gloo. On TPU none of that exists as separate machinery: JAX is
single-controller per host, and parallelism is expressed as *sharding* over a
named multi-axis `jax.sharding.Mesh`:

* ``dp``   — data parallelism: batches sharded on the leading axis, params
  replicated, XLA emits the psum for gradient averaging inside the jitted
  train step;
* ``fsdp`` — data parallelism with parameters/optimizer state ALSO sharded
  (weight-update/ZeRO sharding, arXiv:2004.13336) so big world models fit;
* ``tp``   — tensor parallelism: dense kernels split on a feature dimension.

Axis sizes come from ``fabric.mesh.{dp,fsdp,tp}`` (one axis may be ``-1`` =
auto-fill). Parameter placement is inferred per leaf by the rule engine in
:mod:`sheeprl_tpu.parallel.sharding` — name rules + shape fallbacks, every
decision recorded as a ``sharding`` telemetry event. The historical 1-D
``dp`` layout is exactly the degenerate ``(dp=N, fsdp=1, tp=1)`` case.

`Distributed` owns:
* `jax.distributed.initialize` for multi-host (DCN) runs
* the named `jax.sharding.Mesh` and the per-mesh :class:`SpecEngine`
* sharding helpers (`shard_batch`, `shard_batch_axis`, `shard_params`,
  `shard_opt_state`, `replicate`) and precision policy
* seeding (`seed_everything` → a root `jax.random.key`)

There is no "player vs trainer module" duality (reference ppo/agent.py:278-298
tied-weights pattern): inference reuses the same pure apply fn with the
current params pytree.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import Config
from .sharding import (
    DEFAULT_MIN_SHARD_SIZE,
    MESH_AXES,
    ShardingReport,
    SpecEngine,
    apply_specs,
    infer_tree_specs,
    resolve_mesh_shape,
)

_PRECISION_POLICIES = {
    # name: (param_dtype, compute_dtype). No fp16: it would need loss
    # scaling (the reference pairs Fabric 16-mixed with a GradScaler), and
    # the MXU's native reduced precision is bf16 anyway.
    "32-true": (jnp.float32, jnp.float32),
    "bf16-mixed": (jnp.float32, jnp.bfloat16),
    "bf16-true": (jnp.bfloat16, jnp.bfloat16),
}


@dataclass
class Precision:
    name: str
    param_dtype: Any
    compute_dtype: Any


def get_precision(name: str) -> Precision:
    if name not in _PRECISION_POLICIES:
        raise ValueError(f"Unknown precision '{name}'. Options: {sorted(_PRECISION_POLICIES)}")
    p, c = _PRECISION_POLICIES[name]
    return Precision(name, p, c)


class Distributed:
    """Mesh + sharding + precision context threaded through every algorithm."""

    def __init__(
        self,
        devices: Any = 1,
        accelerator: str = "auto",
        precision: str = "32-true",
        num_nodes: int = 1,
        strategy: str = "auto",
        mesh_axes: Optional[Sequence[str]] = None,
        mesh_shape: Optional[Sequence[int]] = None,
        mesh: Optional[Any] = None,
    ):
        del strategy  # parity knob; sharding subsumes DDP/single-device
        # Multi-host initialization (DCN): driven by standard JAX env vars /
        # TPU metadata; only attempt when explicitly configured.
        if num_nodes > 1 and not jax.distributed.is_initialized():
            jax.distributed.initialize()

        if accelerator in ("auto", None):
            backend = None
        elif accelerator in ("tpu", "gpu", "cuda", "cpu"):
            backend = {"cuda": "gpu"}.get(accelerator, accelerator)
        else:
            raise ValueError(f"Unknown accelerator '{accelerator}'")
        # an accelerator asked for by name and not there is an error
        # (jax.devices raises), never a quiet run on whatever else exists
        all_devices = jax.devices(backend) if backend else jax.devices()

        if devices in ("auto", -1, "-1", None):
            n = len(all_devices)
        else:
            n = int(devices)
        if n > len(all_devices):
            raise RuntimeError(
                f"Requested {n} devices but only {len(all_devices)} available "
                f"({[d.platform for d in all_devices[:4]]}...)"
            )
        self.devices = all_devices[:n]
        self.num_nodes = num_nodes

        def _mesh_get(key: str, default: Any) -> Any:
            if mesh is None:
                return default
            if hasattr(mesh, "get"):
                val = mesh.get(key, default)
            else:
                val = getattr(mesh, key, default)
            return default if val is None else val

        if mesh_axes is not None:
            # legacy/compat 1-D construction (the pre-mesh-subsystem layout;
            # kept for the bit-identity parity test and external callers)
            axes = tuple(mesh_axes)
            if mesh_shape is None:
                mesh_shape = (n,) + (1,) * (len(axes) - 1)
        else:
            axes = MESH_AXES
            mesh_shape = resolve_mesh_shape(
                n,
                dp=int(_mesh_get("dp", -1)),
                fsdp=int(_mesh_get("fsdp", 1)),
                tp=int(_mesh_get("tp", 1)),
            )
        dev_array = np.asarray(self.devices).reshape(tuple(mesh_shape))
        self.mesh = Mesh(dev_array, axes)
        self.axis_sizes: Dict[str, int] = {
            ax: int(sz) for ax, sz in zip(self.mesh.axis_names, self.mesh.devices.shape)
        }
        self.spec_engine = SpecEngine(
            self.axis_sizes,
            min_shard_size=int(_mesh_get("min_shard_size", DEFAULT_MIN_SHARD_SIZE)),
        )
        # ShardingReports accumulated by shard_params/shard_opt_state until a
        # train loop drains them into telemetry (take_sharding_reports)
        self.sharding_reports: List[ShardingReport] = []
        self.precision = get_precision(precision)

    # -- identity ----------------------------------------------------------
    @property
    def world_size(self) -> int:
        return len(self.devices)

    @property
    def dp(self) -> int:
        return self.axis_sizes.get("dp", 1)

    @property
    def fsdp(self) -> int:
        return self.axis_sizes.get("fsdp", 1)

    @property
    def tp(self) -> int:
        return self.axis_sizes.get("tp", 1)

    @property
    def data_parallel_size(self) -> int:
        """How many ways a batch's leading axis shards: dp × fsdp (fsdp is
        data parallelism too; tp replicas see the same batch). Equals
        ``world_size`` on every non-tp mesh — batch-size math that used
        world_size keeps its meaning in the degenerate case."""
        return self.dp * self.fsdp

    @property
    def is_pure_dp(self) -> bool:
        return self.fsdp == 1 and self.tp == 1

    @property
    def process_index(self) -> int:
        return jax.process_index()

    @property
    def is_global_zero(self) -> bool:
        return self.process_index == 0

    @property
    def local_device(self) -> Any:
        return self.devices[0]

    # -- shardings ---------------------------------------------------------
    def sharding(self, *spec: Any) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    @property
    def replicated(self) -> NamedSharding:
        return self.sharding()

    @property
    def batch_sharding(self) -> NamedSharding:
        """Leading-axis sharding over the data axes (dp, and fsdp when the
        mesh has one) — the batch layout of every train loop."""
        return self.shard_batch_axis(0)

    def shard_batch_axis(self, batch_axis: int) -> NamedSharding:
        """Sharding for a batch whose batch dimension sits at ``batch_axis``
        (e.g. 2 for the ``[G, T, B, ...]`` replay batches): the batch dim
        shards over the engine's data axes, everything else replicates.
        This is the ONLY way call sites outside ``parallel/`` place batches
        — specs come from the rule engine, not axis-name literals (the
        ``pspec-literal`` lint rule)."""
        return NamedSharding(self.mesh, self.spec_engine.batch_spec(batch_axis))

    def shard_batch(self, tree: Any) -> Any:
        """Move a host batch to devices, sharded on the leading axis."""
        s = self.batch_sharding
        return jax.tree.map(lambda x: jax.device_put(x, s), tree)

    def replicate(self, tree: Any) -> Any:
        """Every leaf replicated over the mesh. Train loops put ALL the state
        a jitted step carries through here (or a sharded placement) before
        the first call — fresh optimizer counters, `Moments`, a resumed
        checkpoint's numpy leaves — not only the params: an array's type
        includes the mesh of its sharding, so a step whose first call mixes
        mesh-placed params with unplaced leaves gets, from its own outputs,
        different input types on the second call, and traces and compiles
        the whole program again."""
        s = self.replicated
        return jax.tree.map(lambda x: jax.device_put(x, s), tree)

    def shard_params(self, tree: Any, group: str = "params") -> Any:
        """Rule-engine placement for a parameter tree: regex path rules pick
        tp/fsdp layouts per dense-kernel role, shape fallbacks shard big
        leaves over fsdp, small/odd leaves replicate. Every decision lands
        in a :class:`ShardingReport` (drained into ``sharding`` telemetry
        events by the train loop)."""
        specs, report = infer_tree_specs(self.spec_engine, tree, group=group)
        self.sharding_reports.append(report)
        return apply_specs(self.mesh, tree, specs)

    def shard_opt_state(self, tree: Any, min_size: int = DEFAULT_MIN_SHARD_SIZE) -> Any:
        """Optimizer-state placement: moments mirror the param tree's names,
        so sharded params keep matching specs; leaves the rules leave
        replicated fall back to the leading-axis ZeRO-1 layout over the
        fsdp axis (or dp on a pure-dp mesh — the historical
        ``shard_over_dp`` placement, arXiv:2004.13336). Inside the jitted
        train step XLA then computes the moment/EMA updates 1/N-sharded and
        inserts the all-gather for the parameter delta.

        Multi-host runs shard too: checkpointing assembles non-addressable
        shards with a process_allgather collective on every rank
        (utils/checkpoint.py _fetch_global / CheckpointManager.save)."""
        specs, report = infer_tree_specs(
            self.spec_engine, tree, group="opt_state", zero1_fallback=True, zero1_min_size=min_size
        )
        self.sharding_reports.append(report)
        return apply_specs(self.mesh, tree, specs)

    def shard_over_dp(self, tree: Any, min_size: int = DEFAULT_MIN_SHARD_SIZE) -> Any:
        """Compat shim for the pre-mesh-subsystem API: delegates to the rule
        engine's ZeRO-1 optimizer layout. Under ``(dp=N, fsdp=1, tp=1)``
        every placement is identical to the historical implementation
        (leading axis over ``dp`` when it divides and the leaf is big
        enough, replicated otherwise) — asserted by tests/test_mesh_sharding.py."""
        return self.shard_opt_state(tree, min_size=min_size)

    def take_sharding_reports(self) -> List[ShardingReport]:
        """Drain the accumulated reports (train loops emit them as
        ``sharding`` telemetry events once the Telemetry facade exists)."""
        out, self.sharding_reports = self.sharding_reports, []
        return out

    def to_host(self, tree: Any) -> Any:
        return jax.device_get(tree)

    # -- seeding -----------------------------------------------------------
    def seed_everything(self, seed: int) -> jax.Array:
        """Root PRNG key + numpy/python seeding (reference cli.py:187-197)."""
        import random

        random.seed(seed)
        np.random.seed(seed)
        os.environ.setdefault("PYTHONHASHSEED", str(seed))
        return jax.random.key(seed)

    # -- dtype policy ------------------------------------------------------
    def cast_compute(self, tree: Any) -> Any:
        return cast_floating(tree, self.precision.compute_dtype)

    def cast_params(self, tree: Any) -> Any:
        return cast_floating(tree, self.precision.param_dtype)


def cast_floating(tree: Any, dtype: Any) -> Any:
    """Cast every floating leaf of a pytree to `dtype` (PRNG keys, ints and
    bools pass through) — the single mixed-precision cast primitive."""
    return jax.tree.map(
        lambda x: x.astype(dtype)
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
        else x,
        tree,
    )


def build_distributed(cfg: Config) -> Distributed:
    """Build from `cfg.fabric` (group name kept for reference parity)."""
    fab = cfg.get("fabric", Config())
    return Distributed(
        devices=fab.get("devices", 1),
        accelerator=fab.get("accelerator", "auto"),
        precision=str(fab.get("precision", "32-true")),
        num_nodes=int(fab.get("num_nodes", 1)),
        strategy=fab.get("strategy", "auto"),
        mesh=fab.get("mesh", None),
    )


def maybe_shard_opt_state(cfg: Any, dist: Optional["Distributed"], opt_states: Any) -> Any:
    """Optimizer-state layout: on a multi-axis mesh (fsdp or tp > 1) the
    state always follows the rule engine — moments mirror their params'
    inferred specs, replicated leaves get the ZeRO-1 fallback. On a pure-dp
    mesh it is sharded over ``dp`` only when ``fabric.shard_optimizer_state``
    asks for it, and replicated otherwise. Applied once, to fresh AND resumed
    state. Every leaf comes back placed on the mesh: see
    :meth:`Distributed.replicate` for why that matters."""
    if dist is None:
        return opt_states
    if not dist.is_pure_dp:
        return dist.shard_opt_state(opt_states)
    if cfg.select("fabric.shard_optimizer_state", False):
        return dist.shard_over_dp(opt_states)
    return dist.replicate(opt_states)


def maybe_shard_params(cfg: Any, dist: Optional["Distributed"], params: Any) -> Any:
    """Parameter layout: a strict no-op on pure-dp meshes (params stay
    wherever the builder left them — replication is implicit, and the 1-D
    path must remain bit-identical); on a multi-axis mesh every leaf goes
    through the rule engine and is committed to its inferred NamedSharding."""
    del cfg
    if dist is None or dist.is_pure_dp:
        return params
    return dist.shard_params(params)
