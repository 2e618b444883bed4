"""Superepoch evidence smoke for the real chip.

Proves, ON the accelerator, three claims:

1. PARITY — a K-epoch superepoch program reproduces K sequential
   single-epoch programs (same index matrices, same absolute-step RNG
   folds) within the cross-program scan-fusion tolerance;
2. the programs actually compiled here (``superepoch_compiles_total > 0``,
   via the CompileSentry funnel the training loop uses);
3. a REPEATED superepoch call with steady shapes triggers ZERO recompile
   alarms (``superepoch_recompile_alarms_total 0``) — the silent-perf-killer
   check of docs/OBSERVABILITY.md applied to the K-epoch builder.

Prints grep-stable evidence lines + one JSON summary. Exits non-zero when
parity fails, so the stage marker can trust rc=0 + the evidence lines.

Usage: python scripts/superepoch_smoke.py [--k 4] [--steps 4] [--batch 256]
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
import numpy as np

from simclr_tpu.data.cifar import synthetic_dataset
from simclr_tpu.data.pipeline import epoch_index_matrix
from simclr_tpu.models.contrastive import ContrastiveModel
from simclr_tpu.obs.compile import CompileSentry
from simclr_tpu.ops.lars import lars, simclr_weight_decay_mask
from simclr_tpu.parallel.mesh import (
    DATA_AXIS,
    create_mesh,
    put_replicated,
    replicated_sharding,
)
from simclr_tpu.parallel.steps import (
    make_pretrain_epoch_fn,
    make_pretrain_superepoch_fn,
)
from simclr_tpu.parallel.train_state import create_train_state
from simclr_tpu.utils.schedule import warmup_cosine_schedule

PARITY_RTOL = 5e-3  # cross-program scan fusion reorders bf16 roundings


def fresh_state(model, tx, mesh):
    state = create_train_state(
        model, tx, jax.random.key(7), jnp.zeros((2, 32, 32, 3), jnp.float32)
    )
    return jax.device_put(state, replicated_sharding(mesh))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--steps", type=int, default=4, help="steps per epoch")
    ap.add_argument("--batch", type=int, default=256, help="per-device batch")
    args = ap.parse_args()

    mesh = create_mesh()
    n_data = mesh.shape[DATA_AXIS]
    global_batch = args.batch * n_data
    dataset = global_batch * 2
    model = ContrastiveModel(
        base_cnn="resnet18", d=128, bn_cross_replica_axis=DATA_AXIS
    )
    tx = lars(
        warmup_cosine_schedule(0.1, total_steps=10_000, warmup_steps=10),
        weight_decay=1e-4,
        weight_decay_mask=simclr_weight_decay_mask,
    )
    ds = synthetic_dataset("cifar10", "train", size=dataset)
    images_all = put_replicated(ds.images, mesh)
    base_key = jax.random.key(11)
    sentry = CompileSentry()

    epoch_fn = make_pretrain_epoch_fn(
        model, tx, mesh, temperature=0.5, strength=0.5, sentry=sentry
    )
    state_a = fresh_state(model, tx, mesh)
    losses_a = []
    cur = 0
    for epoch in range(1, args.k + 1):
        idx_e = jnp.asarray(
            epoch_index_matrix(dataset, 0, epoch, args.steps, global_batch)
        )
        state_a, hist = epoch_fn(state_a, images_all, idx_e, base_key, cur)
        losses_a.extend(float(x) for x in hist["loss"])
        cur += args.steps

    superepoch_fn = make_pretrain_superepoch_fn(
        model, tx, mesh, temperature=0.5, strength=0.5, sentry=sentry
    )
    idx_super = jnp.asarray(
        np.stack([
            epoch_index_matrix(dataset, 0, e, args.steps, global_batch)
            for e in range(1, args.k + 1)
        ])
    )
    state_b = fresh_state(model, tx, mesh)
    t0 = time.perf_counter()
    state_b, hist = superepoch_fn(state_b, images_all, idx_super, base_key, 0)
    losses_b = [float(x) for x in np.asarray(hist["loss"]).ravel()]
    t_first = time.perf_counter() - t0

    # steady-shape repeat: any compilation here is a recompile alarm
    t0 = time.perf_counter()
    state_b, hist = superepoch_fn(
        state_b, images_all, idx_super, base_key, args.k * args.steps
    )
    float(np.asarray(hist["loss"])[-1, -1])
    t_repeat = time.perf_counter() - t0

    rel = np.abs(np.asarray(losses_b) - np.asarray(losses_a)) / np.maximum(
        np.abs(np.asarray(losses_a)), 1e-9
    )
    max_rel = float(rel.max())
    parity_ok = bool(np.isfinite(losses_b).all()) and max_rel <= PARITY_RTOL

    total = args.k * args.steps
    print(json.dumps({
        "backend": jax.default_backend(),
        "k": args.k,
        "steps_per_epoch": args.steps,
        "global_batch": global_batch,
        "max_rel_loss_diff": round(max_rel, 6),
        "imgs_per_sec_per_chip": round(
            total * global_batch / t_repeat / mesh.size, 1
        ),
        "first_call_s": round(t_first, 2),
        "host_syncs_per_epoch": round(1.0 / args.k, 3),
    }), flush=True)
    print(
        f"superepoch_parity {'OK' if parity_ok else 'FAIL'} "
        f"k={args.k} max_rel_loss_diff={max_rel:.2e}",
        flush=True,
    )
    print(f"superepoch_compiles_total {sentry.compiles}", flush=True)
    print(
        f"superepoch_recompile_alarms_total {sentry.recompile_alarms}",
        flush=True,
    )
    return 0 if parity_ok else 1


if __name__ == "__main__":
    sys.exit(main())
