"""Time NT-Xent implementations standalone on the real chip.

VERDICT r1 #7: the Pallas kernels had only ever run interpreted on CPU.
This times value+grad of the XLA loss (``ntxent_loss``) against the fused
Pallas kernel (``ntxent_loss_fused``) across batch sizes on whatever backend
is available, so `docs/PERF.md` can say when (if ever) fused wins on
hardware. Single-chip: the sharded/ring variants are degenerate at mesh
size 1, so the standalone comparison is XLA-vs-Pallas on the local math;
their collective forms are exercised by the step-level matrix
(scripts/perf_explore.py) and the multichip dry-run.

Usage: python scripts/perf_loss_variants.py [--steps 100]
       [--batches 512,1024,2048,4096] [--d 128]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from simclr_tpu.ops.ntxent import ntxent_loss
from simclr_tpu.ops.ntxent_pallas import ntxent_loss_fused


def time_loss(fn, z0, z1, steps):
    """Time value+grad; the loss fetch closes each timed window."""
    grad_fn = jax.jit(jax.value_and_grad(lambda a, b: fn(a, b, 0.5), argnums=(0, 1)))
    loss, grads = grad_fn(z0, z1)
    float(loss)  # compile + drain
    t0 = time.perf_counter()
    for _ in range(steps):
        loss, grads = grad_fn(z0, z1)
    final = float(loss)  # fence
    dt = time.perf_counter() - t0
    return dt / steps * 1e3, final


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batches", type=str, default="512,1024,2048,4096")
    ap.add_argument("--d", type=int, default=128)
    args = ap.parse_args()

    key = jax.random.key(0)
    for batch in (int(b) for b in args.batches.split(",")):
        k0, k1 = jax.random.split(jax.random.fold_in(key, batch))
        z0 = jax.random.normal(k0, (batch, args.d), jnp.float32)
        z1 = jax.random.normal(k1, (batch, args.d), jnp.float32)
        for name, fn in (("xla", ntxent_loss), ("pallas_fused", ntxent_loss_fused)):
            try:
                ms, loss = time_loss(fn, z0, z1, args.steps)
                print(
                    json.dumps(
                        {
                            "loss_impl": name,
                            "batch": batch,
                            "ms_per_value_and_grad": round(ms, 3),
                            "loss": round(loss, 4),
                            "backend": jax.default_backend(),
                        }
                    ),
                    flush=True,
                )
            except Exception as exc:  # record, keep going
                print(
                    json.dumps(
                        {"loss_impl": name, "batch": batch, "error": repr(exc)[:300]}
                    ),
                    flush=True,
                )


if __name__ == "__main__":
    main()
