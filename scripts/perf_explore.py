"""Perf exploration on real TPU: time pretrain-step variants at batch 512.

Compares forward_mode (two_pass vs concat), fused Pallas NT-Xent, remat,
epoch-compiled scan, and the superepoch K-sweep (one program per K epochs;
reports compile time and host syncs per epoch) against the default step,
each timed window closed by fetching the loss. Prints one JSON line per
variant. A tuning tool, not a benchmark.

Usage: python scripts/perf_explore.py [--steps 100] [--batch 512]
       [--variants two_pass,concat,...]
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
from simclr_tpu.models.contrastive import ContrastiveModel
from simclr_tpu.ops.lars import lars, simclr_weight_decay_mask
from simclr_tpu.parallel.mesh import (
    DATA_AXIS,
    batch_sharding,
    create_mesh,
    put_row_sharded,
    replicated_sharding,
)
from simclr_tpu.parallel.steps import (
    make_pretrain_epoch_fn,
    make_pretrain_step,
    make_pretrain_superepoch_fn,
)
from simclr_tpu.parallel.train_state import create_train_state
from simclr_tpu.utils.profiling import time_step_loop
from simclr_tpu.utils.schedule import calculate_initial_lr, warmup_cosine_schedule

VARIANTS = {
    # name -> kwargs for make_pretrain_step
    "two_pass": dict(forward_mode="two_pass"),
    "concat": dict(forward_mode="concat"),
    "two_pass_fused": dict(forward_mode="two_pass", fused=True),
    "concat_fused": dict(forward_mode="concat", fused=True),
    "two_pass_remat": dict(forward_mode="two_pass", remat=True),
    "epoch_compile": dict(forward_mode="two_pass"),  # scan path, see below
    # sharded dataset residency: N/n_data rows per chip + per-step psum
    # batch assembly — quantifies the collective's cost against the
    # replicated scan (expected <0.1% of step time, docs/PERF.md)
    "epoch_compile_sharded": dict(forward_mode="two_pass"),
    # superepochs (runtime.epochs_per_compile): ONE program per K epochs;
    # sweeps K in SUPEREPOCH_KS and reports compile time and host syncs per
    # epoch (= 1/K) alongside throughput — the Podracer trade, docs/PERF.md
    # "Host round-trip budget"
    "superepoch": dict(forward_mode="two_pass"),
    # augmentation impl sweep (runtime.augment_impl): the vmapped XLA chain
    # vs the fused Pallas one-VMEM-pass kernel inside the full train step —
    # the in-context number next to scripts/augment_bench.py's isolated one
    # (docs/PERF.md "Fused augmentation")
    "augment": dict(forward_mode="two_pass"),
}

AUGMENT_IMPLS = ("xla", "fused")

SUPEREPOCH_KS = (1, 2, 5, 10)


def build_state(model, tx, mesh):
    state = create_train_state(
        model, tx, jax.random.key(7), jnp.zeros((2, 32, 32, 3), jnp.float32)
    )
    return jax.device_put(state, replicated_sharding(mesh))


def time_stepwise(step, state, batches, rng, warmup, steps):
    dt, loss, _ = time_step_loop(step, state, batches, rng, warmup, steps)
    return dt, loss


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--batch", type=int, default=512, help="per-device batch")
    ap.add_argument("--variants", type=str, default=",".join(VARIANTS))
    args = ap.parse_args()

    mesh = create_mesh()
    n_data = mesh.shape[DATA_AXIS]
    global_batch = args.batch * n_data
    model = ContrastiveModel(
        base_cnn="resnet18", d=128, bn_cross_replica_axis=DATA_AXIS
    )
    lr0 = calculate_initial_lr(1.0, args.batch, True)
    schedule = warmup_cosine_schedule(lr0, total_steps=100_000, warmup_steps=10)
    tx = lars(schedule, weight_decay=1e-4, weight_decay_mask=simclr_weight_decay_mask)

    ds = synthetic_dataset("cifar10", "train", size=global_batch * 2)
    sharding = batch_sharding(mesh)
    batches = [
        jax.device_put(ds.images[i * global_batch : (i + 1) * global_batch], sharding)
        for i in range(2)
    ]
    rng = jax.random.key(0)

    for name in args.variants.split(","):
        kw = VARIANTS[name]
        state = build_state(model, tx, mesh)
        if name == "superepoch":
            images_all = jax.device_put(ds.images, replicated_sharding(mesh))
            n = ds.images.shape[0]
            for k in SUPEREPOCH_KS:
                superepoch_fn = make_pretrain_superepoch_fn(
                    model, tx, mesh, temperature=0.5, strength=0.5,
                    negatives="global", **kw,
                )
                # equal timed work per K: K epochs of steps//K steps each
                spe = max(args.steps // k, 1)
                idx = np.random.default_rng(0).integers(
                    0, n, size=(k, spe, global_batch), dtype=np.int32
                )
                idx_d = jax.device_put(
                    jnp.asarray(idx), replicated_sharding(mesh)
                )
                state = build_state(model, tx, mesh)
                t0 = time.perf_counter()
                state, hist = superepoch_fn(
                    state, images_all, idx_d, rng, jnp.int32(0)
                )
                loss = float(hist["loss"][-1, -1])
                t_warm = time.perf_counter() - t0
                t0 = time.perf_counter()
                state, hist = superepoch_fn(
                    state, images_all, idx_d, rng, jnp.int32(0)
                )
                loss = float(hist["loss"][-1, -1])
                dt = time.perf_counter() - t0
                total = k * spe
                print(json.dumps({
                    "variant": f"superepoch_k{k}",
                    "epochs_per_compile": k,
                    "steps_per_epoch": spe,
                    "imgs_per_sec_per_chip": round(
                        total * global_batch / dt / mesh.size, 1
                    ),
                    "ms_per_step": round(dt / total * 1e3, 2),
                    "compile_s": round(max(t_warm - dt, 0.0), 2),
                    # the whole point: boundary fetches per trained epoch
                    "host_syncs_per_epoch": round(1.0 / k, 3),
                    "final_loss": round(loss, 4),
                }), flush=True)
            continue
        if name == "augment":
            for impl in AUGMENT_IMPLS:
                step = make_pretrain_step(
                    model, tx, mesh, temperature=0.5, strength=0.5,
                    negatives="global", augment_impl=impl, **kw,
                )
                state = build_state(model, tx, mesh)
                dt, loss = time_stepwise(
                    step, state, batches, rng, args.warmup, args.steps
                )
                print(json.dumps({
                    "variant": f"augment_{impl}",
                    "augment_impl": impl,
                    "imgs_per_sec_per_chip": round(
                        args.steps * global_batch / dt / mesh.size, 1
                    ),
                    "ms_per_step": round(dt / args.steps * 1e3, 2),
                    "final_loss": round(loss, 4),
                }), flush=True)
            continue
        if name.startswith("epoch_compile"):
            residency = "sharded" if name.endswith("_sharded") else "replicated"
            epoch_fn = make_pretrain_epoch_fn(
                model, tx, mesh, temperature=0.5, strength=0.5,
                negatives="global", residency=residency, **kw,
            )
            images_all = (
                put_row_sharded(ds.images, mesh)
                if residency == "sharded"
                else jax.device_put(ds.images, replicated_sharding(mesh))
            )
            n = ds.images.shape[0]
            steps_per_epoch = args.steps
            idx = np.random.default_rng(0).integers(
                0, n, size=(steps_per_epoch, global_batch), dtype=np.int32
            )
            idx_d = jax.device_put(jnp.asarray(idx), replicated_sharding(mesh))
            # warmup epoch (compile) then timed epoch
            state, hist = epoch_fn(state, images_all, idx_d, rng, jnp.int32(0))
            float(hist["loss"][-1])
            t0 = time.perf_counter()
            state, hist = epoch_fn(state, images_all, idx_d, rng, jnp.int32(0))
            loss = float(hist["loss"][-1])
            dt = time.perf_counter() - t0
        else:
            step = make_pretrain_step(
                model, tx, mesh, temperature=0.5, strength=0.5,
                negatives="global", **kw,
            )
            dt, loss = time_stepwise(
                step, state, batches, rng, args.warmup, args.steps
            )
        rate = args.steps * global_batch / dt / mesh.size
        print(json.dumps({
            "variant": name,
            "imgs_per_sec_per_chip": round(rate, 1),
            "ms_per_step": round(dt / args.steps * 1e3, 2),
            "final_loss": round(loss, 4),
        }), flush=True)


if __name__ == "__main__":
    main()
