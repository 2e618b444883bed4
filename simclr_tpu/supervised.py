"""Fully-supervised baseline entry point.

TPU-native counterpart of ``/root/reference/supervised.py``: same SPMD shape
as pretraining but cross-entropy on :class:`SupervisedModel`, with a
distributed validation pass after every epoch — the reference's
``dist.barrier`` + ``dist.reduce`` sums (``supervised.py:137-139``) become a
``psum`` inside one jitted eval step. Keeps only the best checkpoint by
validation loss or accuracy, deleting the previous best
(``supervised.py:144-162``).

Improvement over the reference, by design (like main.py's):
``experiment.resume=true`` restores the persisted best checkpoint and
continues from its epoch — the reference restarts 200-epoch runs from
scratch on any failure (no checkpoint-load path, SURVEY §5.3).

    python -m simclr_tpu.supervised parameter.epochs=200
"""

from __future__ import annotations

import itertools
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from simclr_tpu.config import Config, check_supervised_conf, load_config, resolve_save_dir
from simclr_tpu.data.cifar import NUM_CLASSES, load_dataset
from simclr_tpu.data.pipeline import EpochIterator
from simclr_tpu.data.prefetch import prefetch
from simclr_tpu.models.contrastive import SupervisedModel
from simclr_tpu.obs.anomaly import maybe_detector
from simclr_tpu.obs.compile import maybe_sentry
from simclr_tpu.obs.device import maybe_dump_oom_profile, maybe_monitor
from simclr_tpu.obs.events import EventLog
from simclr_tpu.obs.exporter import maybe_start_exporter
from simclr_tpu.obs.telemetry import Telemetry
from simclr_tpu.ops.lars import get_weight_decay_mask, lars
from simclr_tpu.parallel.compress import DEFAULT_COMM_CHUNKS, normalize_overlap
from simclr_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    batch_sharding,
    enable_async_collective_flags,
    mesh_from_config,
    mesh_host_count,
    process_local_rows,
    put_global_batch,
    replicated_sharding,
    validate_per_device_batch,
)
from simclr_tpu.parallel.steps import (
    make_supervised_eval_step,
    make_supervised_step,
)
from simclr_tpu.parallel.train_state import create_train_state, param_count
from simclr_tpu.supervisor.guard import (
    PoisonedRun,
    PreemptedRun,
    RunGuard,
    preempt_checkpoint_name,
    resume_point,
)
from simclr_tpu.supervisor.topology import (
    check_resume_topology,
    read_topology,
    write_topology,
)
from simclr_tpu.utils.checkpoint import (
    CheckpointCorruptionError,
    checkpoint_name,
    delete_checkpoint,
    list_checkpoints,
    restore_checkpoint_with_fallback,
    save_checkpoint,
)
from simclr_tpu.utils.logging import get_logger, is_logging_host
from simclr_tpu.utils.profiling import StepTimer, StepTraceWindow
from simclr_tpu.utils.schedule import calculate_initial_lr, warmup_cosine_schedule

logger = get_logger()


def _compute_dtype(cfg: Config):
    name = str(cfg.select("precision.compute_dtype", "bfloat16"))
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def run_supervised(cfg: Config) -> dict:
    check_supervised_conf(cfg)
    seed = int(cfg.parameter.seed)

    comm_overlap = str(
        normalize_overlap(cfg.select("parallel.comm_overlap", "off"))
    )
    comm_chunks = int(cfg.select("parallel.comm_chunks", DEFAULT_COMM_CHUNKS))
    if comm_overlap == "async":
        # must land in XLA_FLAGS before mesh_from_config initializes the
        # backend; no-op off-TPU (parallel/mesh.py)
        enable_async_collective_flags()
    mesh = mesh_from_config(cfg)
    if mesh.shape.get(MODEL_AXIS, 1) > 1 and is_logging_host():
        logger.warning(
            "mesh.model=%d: the supervised baseline has no tensor-parallel "
            "path (the fc head is tiny); model-axis replicas duplicate work. "
            "Prefer mesh.model=1 here.", mesh.shape[MODEL_AXIS],
        )
    global_batch = validate_per_device_batch(int(cfg.experiment.batches), mesh)
    synthetic_ok = bool(cfg.select("experiment.synthetic_data", False))
    data_dir = cfg.select("experiment.data_dir")
    train_ds = load_dataset(
        cfg.experiment.name, "train", data_dir=data_dir, synthetic_ok=synthetic_ok,
        synthetic_size=cfg.select("experiment.synthetic_size"),
        synthetic_noise=cfg.select("experiment.synthetic_noise"),
    )
    val_ds = load_dataset(
        cfg.experiment.name, "test", data_dir=data_dir, synthetic_ok=synthetic_ok,
        synthetic_size=cfg.select("experiment.synthetic_size"),
        synthetic_noise=cfg.select("experiment.synthetic_noise"),
    )
    num_classes = NUM_CLASSES[cfg.experiment.name]

    steps_per_epoch = len(train_ds) // global_batch
    epochs = int(cfg.parameter.epochs)
    total_steps = epochs * steps_per_epoch
    warmup_steps = int(cfg.parameter.warmup_epochs) * steps_per_epoch

    # reference parity scales the base LR by the PER-DEVICE batch
    # (lr_utils.py:11-15); 'global' scales by the full mesh-wide batch (the
    # paper's large-batch LARS recipe, conf/experiment/cifar10-large-batch)
    lr_batch = (
        global_batch
        if str(cfg.select("parameter.lr_scale_batch", "per_device")) == "global"
        else int(cfg.experiment.batches)
    )
    lr0 = calculate_initial_lr(
        float(cfg.experiment.lr),
        lr_batch,
        bool(cfg.parameter.linear_schedule),
    )
    schedule = warmup_cosine_schedule(lr0, total_steps, warmup_steps)
    tx = lars(
        schedule,
        trust_coefficient=0.001,
        weight_decay=float(cfg.experiment.decay),
        weight_decay_mask=get_weight_decay_mask(
            str(cfg.select("optimizer.weight_decay_mask", "structural")),
            str(cfg.experiment.base_cnn),
        ),
        momentum=float(cfg.parameter.momentum),
    )

    model = SupervisedModel(
        base_cnn=cfg.experiment.base_cnn,
        num_classes=num_classes,
        cifar_stem=True,
        dtype=_compute_dtype(cfg),
        bn_cross_replica_axis=DATA_AXIS,
    )
    state = create_train_state(
        model, tx, jax.random.key(seed),
        jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32),
        shardings=replicated_sharding(mesh),
    )

    save_dir = resolve_save_dir(cfg)
    # run telemetry + event timeline (simclr_tpu/obs/, docs/OBSERVABILITY.md),
    # constructed BEFORE the step builders so the compile sentry can watch
    # them
    n_hosts = mesh_host_count(mesh)
    telemetry = Telemetry(
        global_batch=global_batch,
        n_devices=jax.device_count(),
        mesh_hosts=n_hosts,
        grad_allreduce=str(cfg.select("parallel.grad_allreduce", "exact")),
        grad_elements=param_count(state.params),
        allreduce_devices=mesh.shape[DATA_AXIS],
        comm_overlap=comm_overlap,
        comm_chunks=comm_chunks,
    )
    events = EventLog(
        save_dir,
        enabled=bool(cfg.select("telemetry.events", True)) and is_logging_host(),
    )
    # fault-tolerance guard: preemption checkpointing, heartbeat, non-finite
    # loss rollback (simclr_tpu/supervisor/, docs/FAULT_TOLERANCE.md)
    guard = RunGuard(
        save_dir,
        nan_retry_budget=int(cfg.select("supervisor.nan_retry_budget", 2)),
        telemetry=telemetry,
        events=events,
        process_index=jax.process_index(),
    )
    # step anomaly detection (obs/anomaly.py): slow-step classifier + stall
    # watchdog + rate-limited auto-trace, host clock reads only
    detector = (
        maybe_detector(cfg, save_dir, telemetry=telemetry, events=events)
        if is_logging_host() else None
    )
    # compile sentry (obs/compile.py): times/fingerprints/cost-analyzes
    # every step compilation, alarms on post-warmup recompiles. Runs on
    # EVERY host so per-host compile counters feed the fleet view
    sentry = maybe_sentry(
        cfg, telemetry=telemetry, events=events, detector=detector
    )

    eval_step = make_supervised_eval_step(model, mesh)
    data_shard = batch_sharding(mesh)
    train_step = make_supervised_step(
        model, tx, mesh, strength=float(cfg.experiment.strength),
        grad_allreduce=str(cfg.select("parallel.grad_allreduce", "exact")),
        comm_overlap=comm_overlap,
        comm_chunks=comm_chunks,
        augment_impl=str(cfg.select("runtime.augment_impl", "xla")),
        sentry=sentry,
    )
    train_iter = EpochIterator(
        train_ds, global_batch, seed=seed, shuffle=True, sharding=data_shard,
        gather_threads=int(cfg.parameter.num_workers),
    )
    # live HBM accounting (obs/device.py): sampled per scrape from the
    # exporter thread — host-side allocator queries, zero device syncs
    # every host monitors its own local devices' HBM for the fleet view
    monitor = maybe_monitor(cfg, events=events)
    if monitor is not None:
        telemetry.attach_device_monitor(monitor)
    # validation: no shuffle, keep every sample (reference drop_last=False,
    # supervised.py:219-223). The tail remainder is zero-padded to the static
    # batch shape and masked out inside the one jitted eval step — a single
    # code path, same dtype/sharding as full batches, multi-host safe.
    val_steps = math.ceil(len(val_ds) / global_batch)
    val_pad = val_steps * global_batch - len(val_ds)
    val_images = val_ds.images
    val_labels = val_ds.labels
    val_valid = np.ones(len(val_ds), np.float32)
    if val_pad:
        val_images = np.concatenate(
            [val_images, np.zeros((val_pad, *val_images.shape[1:]), val_images.dtype)]
        )
        val_labels = np.concatenate([val_labels, np.zeros(val_pad, val_labels.dtype)])
        val_valid = np.concatenate([val_valid, np.zeros(val_pad, np.float32)])
    val_local = process_local_rows(global_batch)

    def run_validation(st) -> tuple[float, float]:
        """One full distributed validation sweep (reference
        supervised.py:30-58,135-139); the tail batch rides the same jitted
        step via the valid mask."""
        sum_loss, correct, count = 0.0, 0.0, 0.0
        for start in range(0, val_steps * global_batch, global_batch):
            sl = slice(start, start + global_batch)
            totals = eval_step(
                st.params,
                st.batch_stats,
                put_global_batch(val_images[sl][val_local], data_shard),
                put_global_batch(val_labels[sl][val_local], data_shard),
                put_global_batch(val_valid[sl][val_local], data_shard),
            )
            sum_loss += float(totals["sum_loss"])
            correct += float(totals["correct"])
            count += float(totals["count"])
        return sum_loss / max(count, 1.0), correct / max(count, 1.0)

    metric = str(cfg.parameter.metric)
    if is_logging_host():
        os.makedirs(save_dir, exist_ok=True)
        logger.info(
            "supervised %s: %d params, mesh %s, global batch %d, %d epochs, lr0 %.4f",
            cfg.experiment.name, param_count(state.params), dict(mesh.shape),
            global_batch, epochs, lr0,
        )

    base_key = jax.random.key(seed + 1)
    best_value = None
    best_path = None
    best_epoch = 0
    start_epoch = 1
    skip_steps = 0
    events.emit(
        "run_start", entry="supervised", epochs=epochs,
        steps_per_epoch=steps_per_epoch, global_batch=global_batch,
        pid=os.getpid(),
    )
    # Resume (VERDICT r3 item 6) — the same restore→start_epoch mechanism as
    # main.py, adapted to the best-only deletion policy: normally the only
    # checkpoint on disk IS the previous best, so training rewinds to the
    # best epoch; a "-preempt" checkpoint (newer) wins when present. The
    # fallback restore happens BEFORE any stale-checkpoint cleanup — a
    # corrupt newest must be able to fall back to the older one, so deleting
    # first would destroy the very candidates the fallback needs. One
    # re-validation of the restored state re-establishes best_value/best_path
    # so the first post-resume epoch can't spuriously "improve" over None and
    # delete the checkpoint it just resumed from.
    if bool(cfg.select("experiment.resume", False)):
        # the prior generation's topology record, read before this run
        # overwrites the sidecar below (elastic remesh accept/reject)
        prior_topology = read_topology(save_dir)
        t_restore = time.perf_counter()
        restored, ckpt = restore_checkpoint_with_fallback(save_dir, state)
        if restored is not None:
            telemetry.observe_restore(time.perf_counter() - t_restore)
            state = restored
            # best-only invariant restored AFTER the successful restore:
            # drop everything except what we actually resumed from (stale
            # best from a crash window, preempt checkpoints, corrupt newest)
            for stale in list_checkpoints(save_dir):
                if os.path.abspath(stale) != os.path.abspath(ckpt):
                    delete_checkpoint(stale)
            start_epoch, skip_steps = resume_point(
                int(state.step), steps_per_epoch
            )
            # cross-topology resume (elastic remesh): global batch must be
            # preserved and the checkpoint must sit on an epoch boundary —
            # same contract as main.py
            topology_change = check_resume_topology(
                prior_topology,
                n_devices=jax.device_count(),
                n_processes=n_hosts,
                global_batch=global_batch,
                skip_steps=skip_steps,
            )
            if topology_change is not None:
                events.emit("topology_change", **topology_change)
                logger.info(
                    "Cross-topology resume: %d -> %d devices "
                    "(%d -> %d hosts), per-device batch now %d",
                    topology_change["devices_before"],
                    topology_change["devices_after"],
                    topology_change["hosts_before"],
                    topology_change["hosts_after"],
                    topology_change["per_device_batch"],
                )
            val_loss, val_acc = run_validation(state)
            telemetry.observe_val_acc(val_acc)
            best_value = val_loss if metric == "loss" else val_acc
            best_path = ckpt
            best_epoch = start_epoch - 1
            # the resumed epochs re-run: re-seat the timeline so their
            # epoch/checkpoint events are not duplicated
            events.reseat(start_epoch)
            events.emit(
                "resume", epoch=start_epoch, step=int(state.step),
                skip_steps=skip_steps, checkpoint=ckpt,
            )
            if is_logging_host():
                logger.info(
                    "Resumed from %s at epoch %d (best %s=%.4f re-validated)",
                    ckpt, start_epoch, metric, best_value,
                )
    if is_logging_host():
        write_topology(
            save_dir,
            n_devices=jax.device_count(),
            n_processes=n_hosts,
            global_batch=global_batch,
        )
    history = []
    t_start = time.time()
    # host-side mirror of state.step: avoids per-step device sync
    cur_step = (start_epoch - 1) * steps_per_epoch + skip_steps
    # steady-state training throughput like main.py's: validation sweeps and
    # checkpoint I/O are pause()d out of the timed window
    timer = StepTimer(global_batch)
    tracer = StepTraceWindow(
        cfg.select("experiment.profile_dir"),
        start=cur_step + 2,
        length=int(cfg.select("experiment.profile_steps", 10) or 10),
        enabled=is_logging_host(),
    )
    # bound before the loop: a resume whose start_epoch exceeds epochs (the
    # run already completed) must still reach tracer.close/timer.summary
    train_metrics = {"loss": jnp.zeros(()), "accuracy": jnp.zeros(())}
    stem = f"supervised-{cfg.experiment.name}.pt"
    # per-host /metrics + /healthz + /debug/trace exporter (disabled by
    # default — see telemetry.port in conf/supervised_config.yaml); process
    # i>0 publishes telemetry.p<i>.ready for the FleetCollector
    exporter = maybe_start_exporter(
        cfg, telemetry, save_dir, process_index=jax.process_index()
    )
    guard.install_signals()
    try:
        epoch = start_epoch
        while epoch <= epochs:
            epoch_start_step = cur_step
            epoch_t0 = time.perf_counter()
            batches = train_iter.batches(epoch)
            if skip_steps:
                # mid-epoch resume: replay the epoch's deterministic batch
                # order past the consumed prefix (step RNG folds on the
                # absolute cur_step, so the continuation is exact)
                batches = itertools.islice(batches, skip_steps, None)
                skip_steps = 0
            for batch in prefetch(batches):
                tracer.tick(cur_step, pending=train_metrics["loss"])
                step_rng = jax.random.fold_in(base_key, cur_step)
                state, train_metrics = train_step(
                    state, batch["image"], batch["label"], step_rng
                )
                timer.tick(train_metrics["loss"])
                cur_step += 1
                if detector is not None:
                    # BEFORE the beat: the beat is where fault injection
                    # wedges, and the watchdog must already be armed
                    detector.tick(cur_step, epoch)
                guard.beat(cur_step, epoch)
                if guard.preempt_requested:
                    break
            if detector is not None:
                # validation/checkpoint work at the boundary is not a step:
                # disarm so it can never read as a stall
                detector.pause()
            if guard.preempt_requested:
                # land a resumable checkpoint (alongside the untouched best),
                # then exit 75 via main(); resume restores this newest state
                # and re-establishes the best-only invariant
                timer.pause(train_metrics["loss"])
                path = os.path.join(
                    save_dir,
                    preempt_checkpoint_name(cur_step, steps_per_epoch, stem),
                )
                t_save = time.perf_counter()
                save_checkpoint(path, state)
                telemetry.observe_save(time.perf_counter() - t_save)
                events.emit("preempt", step=cur_step, epoch=epoch, checkpoint=path)
                guard.beat_preempted(cur_step, epoch)
                raise PreemptedRun(path)

            epoch_loss = guard.checked_loss(
                cur_step, float(train_metrics["loss"])
            )
            # telemetry BEFORE the beat so the heartbeat snapshot is fresh;
            # host floats only (see obs/telemetry.py) — zero extra syncs,
            # and every host updates its OWN gauges for the fleet view
            telemetry.observe_epoch(
                epoch, epochs=epochs, step=cur_step,
                steps=cur_step - epoch_start_step,
                seconds=time.perf_counter() - epoch_t0,
                loss=epoch_loss,
                lr=float(schedule(max(cur_step - 1, 0))),
            )
            guard.beat(cur_step, epoch, loss=epoch_loss)
            if not math.isfinite(epoch_loss):
                # roll back to the newest verified checkpoint; a different
                # RNG stream on the retry (see main.py)
                try:
                    t_restore = time.perf_counter()
                    rolled, rpath = restore_checkpoint_with_fallback(
                        save_dir, state
                    )
                except CheckpointCorruptionError as e:
                    raise PoisonedRun(str(e)) from e
                guard.record_rollback(epoch_loss, rpath)
                telemetry.observe_restore(time.perf_counter() - t_restore)
                state = rolled
                cur_step = int(state.step)
                epoch, skip_steps = resume_point(cur_step, steps_per_epoch)
                history = [h for h in history if h["epoch"] < epoch]
                # the rolled-back epochs re-run: re-seat the timeline too
                events.reseat(epoch)
                val_loss, val_acc = run_validation(state)
                telemetry.observe_val_acc(val_acc)
                best_value = val_loss if metric == "loss" else val_acc
                best_path = rpath
                best_epoch = epoch - 1
                base_key = jax.random.fold_in(
                    jax.random.key(seed + 1), guard.nan_rollbacks
                )
                continue

            timer.pause(train_metrics["loss"])  # keep eval out of the imgs/sec window
            val_loss, val_acc = run_validation(state)
            telemetry.observe_val_acc(val_acc)
            history.append({"epoch": epoch, "val_loss": val_loss, "val_acc": val_acc})
            events.emit(
                "epoch", epoch=epoch, step=cur_step, loss=epoch_loss,
                val_loss=val_loss, val_acc=val_acc,
                seconds=round(time.perf_counter() - epoch_t0, 6),
            )
            if is_logging_host():
                imgs_per_sec = (
                    (cur_step - (start_epoch - 1) * steps_per_epoch)
                    * global_batch / max(time.time() - t_start, 1e-9)
                )
                logger.info(
                    "Epoch:%d/%d progress:%.3f train_loss:%.3f val_loss:%.4f "
                    "val_acc:%.4f lr:%.7f imgs/sec(cum):%.0f",
                    epoch, epochs, epoch / epochs, epoch_loss,
                    val_loss, val_acc, float(schedule(max(cur_step - 1, 0))),
                    imgs_per_sec,
                )

            # best-only checkpoint policy (reference supervised.py:144-162)
            value = val_loss if metric == "loss" else val_acc
            improved = best_value is None or (
                value < best_value if metric == "loss" else value > best_value
            )
            if improved:
                # save the NEW best before deleting the old one: a crash between
                # the two must leave at least one resumable checkpoint on disk
                # (orbax writes are atomic; epoch-numbered names never collide)
                prev_best = best_path
                best_value = value
                best_epoch = epoch
                best_path = os.path.join(save_dir, checkpoint_name(epoch, stem))
                t_save = time.perf_counter()
                save_checkpoint(best_path, state)
                telemetry.observe_save(time.perf_counter() - t_save)
                events.emit("checkpoint", epoch=epoch, path=best_path)
                guard.after_save(epoch, best_path)
                if prev_best is not None:
                    delete_checkpoint(prev_best)
            timer.resume()
            epoch += 1
    except Exception as exc:
        # allocator RESOURCE_EXHAUSTED: capture the device memory profile +
        # an ``oom`` event before the error propagates (no-op otherwise)
        if is_logging_host():
            maybe_dump_oom_profile(save_dir, exc, events=events)
        raise
    finally:
        guard.restore_signals()
        if detector is not None:
            detector.close()
        if exporter is not None:
            exporter.close()

    tracer.close(pending=train_metrics["loss"])
    throughput = timer.summary()
    if is_logging_host() and throughput["steps"] > 0:
        logger.info(
            "steady-state: %.0f imgs/sec (%.0f per chip) over %d steps",
            throughput["imgs_per_sec"], throughput["imgs_per_sec_per_chip"],
            throughput["steps"],
        )
    summary = {
        "imgs_per_sec_steady": throughput["imgs_per_sec"],
        "best_epoch": best_epoch,
        "best_value": best_value,
        "best_path": best_path,
        "metric": metric,
        "history": history,
        "save_dir": save_dir,
        "steps": int(state.step),
    }
    if is_logging_host():
        import json

        from simclr_tpu.utils.ioutil import atomic_write

        atomic_write(
            os.path.join(save_dir, "supervised_results.json"),
            lambda f: json.dump(summary, f, indent=1),
        )
    events.emit(
        "run_end", step=int(state.step), best_epoch=best_epoch, metric=metric,
    )
    return summary


def main(argv: list[str] | None = None):
    from simclr_tpu.parallel.multihost import maybe_initialize_multihost
    from simclr_tpu.utils.platform import configure_compilation_cache

    configure_compilation_cache()
    maybe_initialize_multihost()
    from simclr_tpu.config import run_multirun, split_multirun_flag
    from simclr_tpu.supervisor.guard import EXIT_POISONED, EXIT_PREEMPTED

    multirun, args = split_multirun_flag(list(sys.argv[1:] if argv is None else argv))
    # exit-code contract (docs/FAULT_TOLERANCE.md): 75 = preempted but
    # resumable, 76 = poisoned (restarting cannot help)
    try:
        if multirun:
            return run_multirun(run_supervised, "supervised_config", args)
        cfg = load_config("supervised_config", overrides=args)
        return run_supervised(cfg)
    except PreemptedRun as e:
        logger.info("%s", e)
        sys.exit(EXIT_PREEMPTED)
    except PoisonedRun as e:
        logger.error("%s", e)
        sys.exit(EXIT_POISONED)


if __name__ == "__main__":
    main()
