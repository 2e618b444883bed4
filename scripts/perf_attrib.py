"""Attribute step time to components: augment / forward / backward / loss /
optimizer.

Times each piece of the pretrain step in isolation, under the same mesh /
shard_map discipline as the real step (collectives included), each window
closed by ``block_until_ready``. For every piece it also pulls XLA's cost
analysis (flops + bytes accessed) from the exact compiled executable, so
each line carries achieved TFLOP/s, achieved GB/s, and arithmetic intensity
— the inputs to a roofline statement against this chip's published peaks
(``simclr_tpu/obs/peaks.py``). Finally times the concat forward against the
two-pass forward to settle why ``forward_mode=concat`` loses at batch 512
despite halved weight streaming (`BENCH_TPU_CAPTURE.json`: 15,822 vs 16,673
imgs/sec).

One JSON line per component + one ``attribution`` summary line, streamed
(flush=True) so a killed run keeps the cells already timed.

Usage: python scripts/perf_attrib.py [--steps 50] [--batch 512] [--d 128]
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

from simclr_tpu.data.cifar import synthetic_dataset
from simclr_tpu.obs.compile import executable_cost as _cost
from simclr_tpu.obs.peaks import chip_peak
from simclr_tpu.models.contrastive import ContrastiveModel
from simclr_tpu.ops.lars import lars, simclr_weight_decay_mask
from simclr_tpu.ops.ntxent import ntxent_loss_sharded_rows
from simclr_tpu.parallel.mesh import (
    DATA_AXIS,
    batch_sharding,
    create_mesh,
    replicated_sharding,
)
from simclr_tpu.parallel.steps import (
    _apply_concat,
    _apply_two_pass,
    _augment_two_views,
    _forward_fn,
    make_pretrain_step,
)
from simclr_tpu.parallel.train_state import create_train_state
from simclr_tpu.utils.schedule import calculate_initial_lr, warmup_cosine_schedule


def _peak():
    """This chip's published peaks (``obs/peaks.py``); ``None`` on CPU, where
    the ``*_pct`` fields are left out."""
    device = jax.devices()[0]
    return chip_peak(device.platform, device.device_kind)


# _cost lives in simclr_tpu.obs.compile now (promoted so the live compile
# sentry and this script extract XLA cost identically); alias kept so every
# call site and the emitted JSON stay byte-identical.


def time_compiled(compiled, args_, steps):
    """ms/iter of a lowered+compiled fn (drain, then timed window, fenced)."""
    out = compiled(*args_)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = compiled(*args_)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps * 1e3


def emit(name, ms, flops, bytes_acc, extra=None):
    line = {
        "component": name,
        "ms": round(ms, 3),
        "backend": jax.default_backend(),
    }
    peak = _peak()
    if flops:
        tflops = flops / (ms * 1e-3) / 1e12
        line["tflops_per_sec"] = round(tflops, 2)
        if peak:
            line["mfu_pct"] = round(
                100 * tflops * 1e12 / peak.bf16_flops_per_s, 1
            )
    if bytes_acc:
        gbps = bytes_acc / (ms * 1e-3) / 1e9
        line["gbytes_per_sec"] = round(gbps, 1)
        if peak:
            line["hbm_pct"] = round(100 * gbps * 1e9 / peak.hbm_bytes_per_s, 1)
    if flops and bytes_acc:
        line["ai_flops_per_byte"] = round(flops / bytes_acc, 2)
    line.update(extra or {})
    print(json.dumps(line), flush=True)
    return line


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=512, help="per-device batch")
    ap.add_argument("--d", type=int, default=128)
    args = ap.parse_args()

    mesh = create_mesh()
    n_data = mesh.shape[DATA_AXIS]
    global_batch = args.batch * n_data
    rep = replicated_sharding(mesh)
    bsh = batch_sharding(mesh)

    model = ContrastiveModel(base_cnn="resnet18", d=args.d,
                             bn_cross_replica_axis=DATA_AXIS)
    lr0 = calculate_initial_lr(1.0, args.batch, True)
    tx = lars(warmup_cosine_schedule(lr0, 100_000, 10), weight_decay=1e-4,
              weight_decay_mask=simclr_weight_decay_mask)
    state = create_train_state(
        model, tx, jax.random.key(7), jnp.zeros((2, 32, 32, 3), jnp.float32)
    )
    state = jax.device_put(state, rep)

    ds = synthetic_dataset("cifar10", "train", size=global_batch)
    images = jax.device_put(ds.images[:global_batch], bsh)
    rng = jax.device_put(jax.random.key(0), rep)

    results = {}
    fwd = _forward_fn(model, remat=False)

    # --- full step (the default pretrain program) ------------------------
    step = make_pretrain_step(model, tx, mesh, temperature=0.5, strength=0.5,
                              negatives="global")
    c = step.lower(state, images, rng).compile()
    fl, by = _cost(c)
    # time via a non-donating wrapper is wrong (donation); reuse output state
    out_state, _ = c(state, images, rng)
    jax.block_until_ready(out_state.step)
    t0 = time.perf_counter()
    s = out_state
    for _ in range(args.steps):
        s, m = c(s, images, rng)
    jax.block_until_ready(m["loss"])
    ms_full = (time.perf_counter() - t0) / args.steps * 1e3
    results["full_step"] = emit("full_step", ms_full, fl, by)
    state = jax.device_put(jax.device_get(s), rep)  # fresh undonated copy

    def shmap(f, in_specs, out_specs):
        from jax.sharding import PartitionSpec as P
        spec = {"rep": P(), "batch": P(DATA_AXIS)}
        return jax.jit(jax.shard_map(
            f, mesh=mesh,
            in_specs=tuple(spec[s] for s in in_specs),
            out_specs=jax.tree.map(lambda s: spec[s], out_specs),
            check_vma=False,
        ))

    # --- augment only ----------------------------------------------------
    aug = shmap(lambda r, im: _augment_two_views(
        jax.random.fold_in(r, jax.lax.axis_index(DATA_AXIS)), im, 0.5, 32),
        ("rep", "batch"), ("batch", "batch"))
    c = aug.lower(rng, images).compile()
    fl, by = _cost(c)
    results["augment"] = emit("augment", time_compiled(c, (rng, images), args.steps), fl, by)

    # pre-augmented views for the forward/backward pieces — reuse the
    # compiled executable (a fresh `aug(...)` call would re-trace+compile)
    v0, v1 = c(rng, images)

    # --- two forwards, no grad (train-mode BN incl. cross-replica pmean) -
    def fwd2(params, stats, a, b):
        z0, z1, _ = _apply_two_pass(fwd, params, stats, a, b)
        return z0, z1

    f2 = shmap(fwd2, ("rep", "rep", "batch", "batch"), ("batch", "batch"))
    c = f2.lower(state.params, state.batch_stats, v0, v1).compile()
    fl, by = _cost(c)
    results["forward_2x"] = emit(
        "forward_2x", time_compiled(c, (state.params, state.batch_stats, v0, v1), args.steps), fl, by)

    # --- concat forward (the forward_mode=concat core) -------------------
    def fwdcat(params, stats, a, b):
        z0, z1, _ = _apply_concat(fwd, params, stats, a, b)
        return z0, z1

    fc = shmap(fwdcat, ("rep", "rep", "batch", "batch"), ("batch", "batch"))
    c = fc.lower(state.params, state.batch_stats, v0, v1).compile()
    fl, by = _cost(c)
    results["forward_concat"] = emit(
        "forward_concat", time_compiled(c, (state.params, state.batch_stats, v0, v1), args.steps), fl, by)

    # --- forward+backward incl. loss and grad psum, no optimizer ---------
    def fb(params, stats, a, b):
        def loss_fn(p):
            z0, z1, _ = _apply_two_pass(fwd, p, stats, a, b)
            return ntxent_loss_sharded_rows(z0, z1, DATA_AXIS, 0.5)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        return loss, jax.lax.psum(grads, DATA_AXIS)

    fbj = shmap(fb, ("rep", "rep", "batch", "batch"), ("rep", "rep"))
    c = fbj.lower(state.params, state.batch_stats, v0, v1).compile()
    fl, by = _cost(c)
    results["fwd_bwd"] = emit(
        "fwd_bwd", time_compiled(c, (state.params, state.batch_stats, v0, v1), args.steps), fl, by)
    _, grads = c(state.params, state.batch_stats, v0, v1)

    # --- loss value+grad on fixed embeddings (global negatives) ----------
    z0 = jax.device_put(jax.random.normal(jax.random.key(1), (global_batch, args.d)), bsh)
    z1 = jax.device_put(jax.random.normal(jax.random.key(2), (global_batch, args.d)), bsh)

    def lg(a, b):
        return jax.value_and_grad(
            lambda x, y: ntxent_loss_sharded_rows(x, y, DATA_AXIS, 0.5),
            argnums=(0, 1))(a, b)

    lj = shmap(lg, ("batch", "batch"), ("rep", ("batch", "batch")))
    c = lj.lower(z0, z1).compile()
    fl, by = _cost(c)
    results["loss_grad"] = emit("loss_grad", time_compiled(c, (z0, z1), args.steps), fl, by)

    # --- LARS update on fixed grads --------------------------------------
    def upd(g, opt_state, params):
        import optax
        updates, new_opt = tx.update(g, opt_state, params)
        return optax.apply_updates(params, updates), new_opt

    uj = jax.jit(upd)
    c = uj.lower(grads, state.opt_state, state.params).compile()
    fl, by = _cost(c)
    results["lars_update"] = emit(
        "lars_update", time_compiled(c, (grads, state.opt_state, state.params), args.steps), fl, by)

    # --- attribution summary ---------------------------------------------
    full = results["full_step"]["ms"]
    fwd_ms = results["forward_2x"]["ms"]
    bwd_ms = max(results["fwd_bwd"]["ms"] - fwd_ms, 0.0)
    acc = {
        "augment": results["augment"]["ms"],
        "forward": fwd_ms,
        "backward_incl_loss": bwd_ms,
        "lars": results["lars_update"]["ms"],
    }
    resid = full - sum(acc.values())
    print(json.dumps({
        "attribution": {k: round(v, 3) for k, v in acc.items()},
        "residual_ms": round(resid, 3),
        "full_step_ms": full,
        "pct": {k: round(100 * v / full, 1) for k, v in acc.items()},
        "concat_vs_two_pass_fwd_ms": [
            results["forward_concat"]["ms"], results["forward_2x"]["ms"]],
        "backend": jax.default_backend(),
        "note": "pieces timed in isolation; residual = fusion overlap the "
                "full program gains/loses vs the sum of parts",
    }), flush=True)


if __name__ == "__main__":
    main()
