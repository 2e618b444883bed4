"""Multi-host dryrun WORKER: rendezvous + chunked ring.

One JAX process of a (possibly) multi-process job. Run under
``simclr_tpu.launch`` (which exports the ``JAX_COORDINATOR_ADDRESS`` /
``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID`` rendezvous convention) or
standalone as the single-process reference. The driver is
``scripts/multihost_dryrun.py``; this module is the payload it launches on
both sides of the parity comparison.

What it exercises, deliberately end to end:

  1. ``maybe_initialize_multihost`` — the real rendezvous path (honoring
     ``JAX_COORDINATOR_TIMEOUT_S`` so a wedged coordinator fails fast);
  2. ``compress.grad_allreduce(..., overlap="chunked")`` — the chunked
     ppermute ring across the full global mesh, int8 wire format, with the
     per-device key convention the train step uses.

The checksum depends only on LOGICAL axis indices and the shared PRNG key,
never on which process hosts which device — so a 2-process 4+4-device run
must reproduce the 1-process 8-device run bitwise. That is the parity the
``multihost_dryrun`` watcher stage asserts.

Prints exactly one JSON line from process 0:

    {"worker": "multihost_dryrun", "process_count": N, "n_devices": D,
     "checksum": ..., "local_total": ...}
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from simclr_tpu.parallel import compress
from simclr_tpu.parallel.mesh import DATA_AXIS, MeshSpec, create_mesh
from simclr_tpu.parallel.multihost import maybe_initialize_multihost
from simclr_tpu.utils.platform import configure_compilation_cache

COMM_CHUNKS = 3  # non-divisible into the flat length: exercises the tail


def run() -> dict:
    maybe_initialize_multihost()
    mesh = create_mesh(MeshSpec(data=-1, model=1))

    def body():
        # a gradient-shaped vector built from THIS shard's logical index
        # alone — physical device/process placement cancels out, and a
        # misrouted hop shifts the reduced vector and breaks parity
        i = jax.lax.axis_index(DATA_AXIS)
        g = (1.0 + i.astype(jnp.float32)) * jnp.linspace(
            -1.0, 1.0, 257, dtype=jnp.float32
        ) + 0.01 * i.astype(jnp.float32)
        key = jax.random.fold_in(jax.random.key(0), i)
        out = compress.grad_allreduce(
            {"g": g}, DATA_AXIS, "int8",
            key=jax.random.fold_in(key, compress.KEY_FOLD_QUANT),
            overlap="chunked", chunks=COMM_CHUNKS,
        )["g"]
        return jnp.sum(out)[None]

    fn = jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=(), out_specs=P(DATA_AXIS),
            check_vma=False,
        )
    )
    per_shard = fn()
    # per-shard sums of a replica-identical result; fetch this process's
    # addressable piece and psum on host via the replicated total
    checksum = float(jnp.sum(per_shard.addressable_shards[0].data))
    total = float(
        np.sum([np.asarray(s.data).sum() for s in per_shard.addressable_shards])
    )
    return {
        "worker": "multihost_dryrun",
        "process_count": jax.process_count(),
        "n_devices": jax.device_count(),
        # replica-identical ring output => every shard sums the same reduced
        # vector, so shard 0's sum IS the global checksum on every process
        "checksum": checksum,
        "local_total": total,
    }


def main() -> None:
    configure_compilation_cache()
    result = run()
    if jax.process_index() == 0:
        print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
