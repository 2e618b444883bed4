"""Device mesh construction and canonical shardings.

Replaces the reference's ``distributed`` config group + NCCL world
(``/root/reference/conf/distributed/base.yaml``,
``/root/reference/distributed_utils.py:8-24``) with a declarative mesh spec:

    mesh:
      data: -1     # data-parallel axis (grad psum, BN pmean, NT-Xent gather)
      model: 1     # tensor-parallel axis, reserved

``-1`` means "all remaining devices", so the same config runs on 1 chip, a
v4-8 slice, or a multi-host pod without edits — world size is discovered from
the runtime, never passed per-process the way the reference's launcher
injects ``distributed.world_size`` overrides (``launch.py:246-248``).
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"

# XLA flags that let the latency-hiding scheduler actually hide the async
# ring hops emitted by comm_overlap=async: async lowering of the collective
# primitives the ring uses (ppermute, all-gather, all-reduce) plus the
# scheduler itself. TPU-only — CPU/GPU jaxlib rejects unknown --xla_tpu_*
# flags as fatal, so enable_async_collective_flags() gates on the platform.
ASYNC_COLLECTIVE_XLA_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_enable_async_collective_permute=true",
    "--xla_enable_async_all_gather=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_tpu_enable_latency_hiding_scheduler=true",
)


def enable_async_collective_flags(env=None, *, platform: str | None = None) -> bool:
    """Append :data:`ASYNC_COLLECTIVE_XLA_FLAGS` to ``XLA_FLAGS`` (idempotent).

    Must run BEFORE the jax backend initializes — which is why the platform
    check reads the environment (``JAX_PLATFORMS`` / ``TPU_NAME`` /
    ``TPU_WORKER_ID``) instead of ``jax.default_backend()``: asking jax for
    the backend would initialize it and freeze ``XLA_FLAGS`` too early.
    Returns True when the flags are (already) in effect, False when skipped
    off-TPU. ``env``/``platform`` exist for tests.
    """
    env = os.environ if env is None else env
    if platform is None:
        declared = env.get("JAX_PLATFORMS", "") or env.get("JAX_PLATFORM_NAME", "")
        if "tpu" in declared.lower():
            platform = "tpu"
        elif declared:
            platform = declared.split(",")[0].strip().lower()
        elif env.get("TPU_NAME") or env.get("TPU_WORKER_ID"):
            platform = "tpu"
        else:
            platform = "unknown"
    if platform != "tpu":
        return False
    current = env.get("XLA_FLAGS", "")
    missing = [f for f in ASYNC_COLLECTIVE_XLA_FLAGS if f not in current]
    if missing:
        env["XLA_FLAGS"] = " ".join(filter(None, [current, *missing]))
    return True


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape; -1 axes absorb the remaining devices."""

    data: int = -1
    model: int = 1

    def resolve(self, n_devices: int) -> tuple[int, int]:
        data, model = self.data, self.model
        if data == -1 and model == -1:
            raise ValueError("at most one mesh axis may be -1")
        if model == -1:
            if n_devices % max(data, 1):
                raise ValueError(f"data={data} does not divide {n_devices} devices")
            model = n_devices // data
        if data == -1:
            if n_devices % max(model, 1):
                raise ValueError(f"model={model} does not divide {n_devices} devices")
            data = n_devices // model
        if data * model != n_devices:
            raise ValueError(
                f"mesh {data}x{model} != {n_devices} available devices; "
                f"use -1 to absorb remaining devices"
            )
        return data, model


def create_mesh(
    spec: MeshSpec | None = None, devices: Sequence[jax.Device] | None = None
) -> Mesh:
    """Build a 2-D (data, model) mesh over the given (default: all) devices.

    On TPU ``mesh_utils.create_device_mesh`` orders devices so that
    neighboring mesh coordinates are ICI neighbors (ring-friendly
    collectives), and a shape it cannot lay out is an error. CPU devices
    have no topology: the virtual test mesh is a plain reshape.
    """
    spec = spec or MeshSpec()
    devices = list(devices if devices is not None else jax.devices())
    data, model = spec.resolve(len(devices))
    if devices[0].platform == "cpu":
        device_grid = np.asarray(devices).reshape(data, model)
    else:
        device_grid = mesh_utils.create_device_mesh(
            (data, model), devices=np.asarray(devices)
        )
    return Mesh(device_grid, (DATA_AXIS, MODEL_AXIS))


def mesh_from_config(cfg, devices: Sequence[jax.Device] | None = None) -> Mesh:
    """Mesh from the ``mesh:`` config group (``conf/mesh/base.yaml``)."""
    node = cfg.select("mesh")
    spec = MeshSpec(
        data=int(node.get("data", -1)) if node is not None else -1,
        model=int(node.get("model", 1)) if node is not None else 1,
    )
    return create_mesh(spec, devices=devices)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Batch-dim sharding over the data axis (replicated over model)."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding (params, opt state, scalars)."""
    return NamedSharding(mesh, P())


def put_global_batch(local_rows: np.ndarray, sharding: NamedSharding) -> jax.Array:
    """Host-local batch rows -> globally batch-sharded device array.

    The multi-host-safe replacement for ``jax.device_put(x, batch_sharding)``:
    a process cannot ``device_put`` onto a sharding that spans devices it does
    not address. Every process passes its own contiguous row block (process
    ``p`` holds global rows ``[p*k, (p+1)*k)``, the convention shared with
    ``data.pipeline.EpochIterator``), and the global array is assembled with
    ``make_array_from_process_local_data``. Single-process: a plain
    ``device_put``. Inverse of ``multihost_utils.process_allgather(tiled=True)``.
    """
    if jax.process_count() > 1:
        global_shape = (local_rows.shape[0] * jax.process_count(), *local_rows.shape[1:])
        return jax.make_array_from_process_local_data(
            sharding, np.asarray(local_rows), global_shape
        )
    return jax.device_put(local_rows, sharding)


def serve_replica_devices(replicas: int = -1) -> list[jax.Device]:
    """The serve tier's replica placement: the first ``replicas`` local devices.

    ``-1`` (the ``serve.replicas`` default) means one replica per local
    device — the same "absorb what the runtime has" convention as
    :class:`MeshSpec`, so one config serves a laptop and a v4-8 slice.
    Local (not global) devices: each serve process owns its own replicas;
    multi-host serving is N independent processes behind a load balancer,
    not one SPMD program.
    """
    devices = jax.local_devices()
    if replicas in (-1, 0):
        return list(devices)
    if not 1 <= replicas <= len(devices):
        raise ValueError(
            f"serve.replicas={replicas} but only {len(devices)} local "
            f"devices are available (use -1 for one replica per device)"
        )
    return list(devices[:replicas])


def retrieval_mesh(devices: Sequence[jax.Device] | None = None) -> Mesh:
    """Data-axis-only mesh over the local devices, for the serve tier's
    row-sharded embedding corpus (``serve/retrieval.py``). The corpus
    shards over every local device regardless of ``serve.replicas`` — HBM
    residency and replica count size independently."""
    return create_mesh(
        MeshSpec(data=-1, model=1),
        devices=list(devices if devices is not None else jax.local_devices()),
    )


def process_local_rows(n_global_rows: int) -> slice:
    """This process's contiguous row block of a batch of ``n_global_rows``.

    Pairs with :func:`put_global_batch`: ``put_global_batch(x[process_local_rows
    (len(x))], s)`` uploads a host-replicated array ``x`` as a globally
    batch-sharded one.
    """
    n_proc = jax.process_count()
    if n_global_rows % n_proc:
        raise ValueError(
            f"batch of {n_global_rows} rows not divisible by {n_proc} processes"
        )
    per_proc = n_global_rows // n_proc
    start = jax.process_index() * per_proc
    return slice(start, start + per_proc)


def local_batch_size(global_batch: int, mesh: Mesh) -> int:
    n_data = mesh.shape[DATA_AXIS]
    if global_batch % n_data:
        raise ValueError(
            f"global batch {global_batch} not divisible by data axis {n_data}"
        )
    return global_batch // n_data


def num_data_shards(mesh: Mesh) -> int:
    return mesh.shape[DATA_AXIS]


def mesh_host_count(mesh: Mesh) -> int:
    """Distinct host processes backing the mesh's devices — the value of the
    ``simclr_train_mesh_hosts`` gauge and the denominator of every elastic
    remesh decision. Counted from the mesh itself (not ``process_count()``)
    so a mesh deliberately built over a device subset reports its own
    footprint."""
    return len({d.process_index for d in mesh.devices.flat})


def validate_per_device_batch(per_device_batch: int, mesh: Mesh) -> int:
    """Global batch from the reference's per-device semantics.

    The reference's ``experiment.batches`` is the PER-GPU batch and global
    batch is ``batches * world_size`` (``/root/reference/main.py:77``,
    ``conf/experiment/cifar10.yaml:10``); we keep those semantics with the
    data-axis size standing in for world size.
    """
    if per_device_batch <= 0:
        raise ValueError("per-device batch must be positive")
    return per_device_batch * num_data_shards(mesh)
