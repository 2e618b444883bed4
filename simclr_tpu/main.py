"""Contrastive pretraining entry point (SimCLR NT-Xent).

TPU-native counterpart of ``/root/reference/main.py``: where the reference
spawns one process per GPU via the vendored launcher and wraps the model in
SyncBN+DDP (``main.py:134-180``), this is ONE SPMD program — a mesh over all
chips, a jit-compiled train step (augment → two forwards → NT-Xent → psum
grads → LARS) and a host loop that only feeds raw uint8 batches and logs.

Usage (same override surface as the reference, ``README.md:17-21``):

    python -m simclr_tpu.main parameter.epochs=200 experiment.batches=512

Improvements over the reference, by design: full train-state checkpointing
with resume (the reference is save-only, SURVEY §5.3-4), and a final-epoch
checkpoint even when ``epochs % save_model_epoch != 0``.
"""

from __future__ import annotations

import functools
import itertools
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp

from simclr_tpu.config import (
    Config,
    check_pretrain_conf,
    load_config,
    resolve_save_dir,
)
from simclr_tpu.data.cifar import load_dataset
from simclr_tpu.data.documents import DATASET_NAME as DOCUMENTS, synthetic_documents
from simclr_tpu.data.pipeline import EpochIterator
from simclr_tpu.data.prefetch import prefetch
from simclr_tpu.models.arch import SEQUENCE_ENCODERS
from simclr_tpu.models.contrastive import ContrastiveModel
from simclr_tpu.models.sequence import PackedView
from simclr_tpu.obs.anomaly import maybe_detector
from simclr_tpu.obs.compile import maybe_sentry
from simclr_tpu.obs.device import maybe_dump_oom_profile, maybe_monitor
from simclr_tpu.obs.events import EventLog
from simclr_tpu.obs.exporter import maybe_start_exporter
from simclr_tpu.obs.peaks import chip_peak
from simclr_tpu.obs.telemetry import Telemetry
from simclr_tpu.obs.trace import recorder, spans_path
from simclr_tpu.ops.lars import get_weight_decay_mask, lars
from simclr_tpu.parallel.compress import DEFAULT_COMM_CHUNKS, normalize_overlap
from simclr_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    batch_sharding,
    enable_async_collective_flags,
    mesh_from_config,
    mesh_host_count,
    replicated_sharding,
    validate_per_device_batch,
)
from simclr_tpu.parallel.steps import make_pretrain_step
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
    restore_checkpoint_with_fallback,
    save_checkpoint,
)
from simclr_tpu.utils.logging import get_logger, is_logging_host
from simclr_tpu.utils.platform import (
    backend_compilations,
    compilation_cache_stats,
    device_identity,
)
from simclr_tpu.utils.profiling import StepTimer, StepTraceWindow
from simclr_tpu.utils.schedule import calculate_initial_lr, warmup_cosine_schedule

logger = get_logger()


def _compute_dtype(cfg: Config):
    name = str(cfg.select("precision.compute_dtype", "bfloat16"))
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def build_model(cfg: Config) -> ContrastiveModel:
    return ContrastiveModel(
        base_cnn=cfg.experiment.base_cnn,
        d=cfg.parameter.d,
        cifar_stem=True,
        dtype=_compute_dtype(cfg),
        bn_cross_replica_axis=DATA_AXIS,
    )


def load_train_rows(cfg: Config):
    """The training split by ``experiment.name``: CIFAR images, or the
    seeded token documents a sequence encoder trains on
    (``data/documents.py``), over the encoder's held vocabulary slice."""
    if cfg.experiment.name != DOCUMENTS:
        return load_dataset(
            cfg.experiment.name,
            "train",
            data_dir=cfg.select("experiment.data_dir"),
            synthetic_ok=bool(cfg.select("experiment.synthetic_data", False)),
            synthetic_size=cfg.select("experiment.synthetic_size"),
            synthetic_noise=cfg.select("experiment.synthetic_noise"),
        )
    return synthetic_documents(
        int(cfg.experiment.documents), int(cfg.experiment.document_tokens),
        SEQUENCE_ENCODERS[cfg.experiment.base_cnn].vocab_held,
    )


def sample_input(crop_lengths: tuple[int, ...]):
    """Shapes of one model input, for the state's initialisation: two
    images, or one packed row of token documents (``crop_lengths`` given)."""
    if not crop_lengths:
        return jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32)
    row = jax.ShapeDtypeStruct((1, sum(crop_lengths)), jnp.int32)
    return PackedView(
        row, row, row, jax.ShapeDtypeStruct((1, len(crop_lengths)), jnp.int32)
    )


def expert_counters(metrics: dict) -> dict:
    """A step's expert-layer counters as the ``epoch`` event carries them
    (fetched with the loss; absent for an encoder without experts):
    ``held_pairs``, the (token, expert) pairs the held experts computed in
    the step; ``expert_load_max_over_mean``, the fullest held expert's pairs
    over the mean held expert's, in the layer where that is largest;
    ``expert_chunks_per_call``, the chunks of sorted pairs the step's expert
    layers ran over their calls (``models/experts.py``: 1.0 where every
    call's held pairs fit one chunk)."""
    if "expert_load" not in metrics:
        return {}
    load, chunks, calls = (
        leaf.astype(float) for leaf in jax.device_get(
            [metrics[name] for name in ("expert_load", "expert_chunks", "expert_calls")]
        )
    )  # (layers, held), (layers,), (layers,)
    return {
        "held_pairs": int(load.sum()),
        "expert_load_max_over_mean": float(
            (load.max(axis=1) / load.mean(axis=1).clip(min=1.0)).max()
        ),
        "expert_chunks_per_call": float(chunks.sum() / calls.sum()),
    }


def leads_over_boundary(
    epoch: int, *, epochs: int, save_model_epoch: int, eval_every: int
) -> bool:
    """Whether the loop may queue the next epoch's first step before
    it fetches ``epoch``'s loss, so that the device runs that step while the
    host does the boundary's work. Not where the boundary has to see the state
    as the epoch left it (the step donates it): a checkpoint or a probe is
    due, or no epoch follows."""
    if epoch >= epochs or epoch % save_model_epoch == 0:
        return False
    return not (eval_every > 0 and epoch % eval_every == 0)


def run_pretrain(cfg: Config) -> dict:
    """Train; returns a summary dict (final loss, steps, save_dir)."""
    check_pretrain_conf(cfg)
    seed = int(cfg.parameter.seed)
    # host spans (obs/trace.py): always on, in memory until a flush. main()
    # opened boot/runtime at its entry; a caller that came another way
    # starts with an empty recorder and the span covers the backend start
    spans = recorder()
    boot_span = spans.current()
    if boot_span is None or boot_span.name != "boot/runtime":
        spans.reset()
        boot_span = spans.begin("boot/runtime")

    comm_overlap = str(
        normalize_overlap(cfg.select("parallel.comm_overlap", "off"))
    )
    comm_chunks = int(cfg.select("parallel.comm_chunks", DEFAULT_COMM_CHUNKS))
    if comm_overlap == "async":
        # must land in XLA_FLAGS before mesh_from_config initializes the
        # backend; no-op off-TPU (parallel/mesh.py)
        enable_async_collective_flags()
    mesh = mesh_from_config(cfg)
    spans.end(boot_span)
    n_data = mesh.shape[DATA_AXIS]
    global_batch = validate_per_device_batch(int(cfg.experiment.batches), mesh)

    with spans.span("boot/data"):
        dataset = load_train_rows(cfg)
    boot_span = spans.begin("boot/state")  # optimizer, model, state: to run_start

    # Reference step accounting (drop_last truncation, main.py:76-80)
    steps_per_epoch = len(dataset) // global_batch
    if steps_per_epoch == 0:
        # early, before any compile; EpochIterator repeats this
        raise ValueError(
            f"dataset of {len(dataset)} samples smaller than global batch "
            f"{global_batch}"
        )
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

    model = build_model(cfg)
    n_model = mesh.shape[MODEL_AXIS]
    crop_lengths = tuple(
        int(n) for n in cfg.select("experiment.crop_lengths", None) or ()
    )
    if n_model > 1:
        # tensor-parallel layout from the start: head leaves sharded over the
        # model axis, everything else replicated (parallel/tp.py); also the
        # restore template, so resume keeps the layout
        from simclr_tpu.parallel.tp import tp_state_shardings

        state_shardings = functools.partial(tp_state_shardings, mesh)
    else:
        state_shardings = replicated_sharding(mesh)
    # one compiled program whose outputs are placed at their shardings; n is
    # the backend compilations JAX reported inside (1, or 0 from its
    # in-process cache: the eager init it replaced made about 160-290)
    init_key = jax.random.key(seed)
    with spans.span("boot/state/init") as init_span:
        compiled_before = backend_compilations()
        state = create_train_state(
            model, tx, init_key, sample_input(crop_lengths),
            shardings=state_shardings,
        )
        init_span.n = backend_compilations() - compiled_before

    save_dir = resolve_save_dir(cfg)
    # run telemetry (simclr_tpu/obs/, docs/OBSERVABILITY.md): metric
    # registry + events.jsonl timeline, fed only host floats the loop
    # already fetches — scraping adds zero device syncs
    n_hosts = mesh_host_count(mesh)
    device = device_identity()
    peak = chip_peak(device["platform"], device["kind"])
    telemetry = Telemetry(
        global_batch=global_batch,
        n_devices=jax.device_count(),
        mesh_hosts=n_hosts,
        grad_allreduce=str(cfg.select("parallel.grad_allreduce", "exact")),
        grad_elements=param_count(state.params),
        allreduce_devices=n_data,
        comm_overlap=comm_overlap,
        comm_chunks=comm_chunks,
        peak_flops=peak.bf16_flops_per_s if peak else None,
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
    # step anomaly detection (obs/anomaly.py): rolling median/MAD slow-step
    # classifier + stall watchdog + rate-limited auto-trace — host clock
    # reads only, zero extra device syncs
    detector = (
        maybe_detector(cfg, save_dir, telemetry=telemetry, events=events)
        if is_logging_host() else None
    )
    # compile sentry (obs/compile.py): every lower/compile of the step
    # functions is timed, fingerprinted, and cost-analyzed; a post-warmup
    # recompile raises the alarm and reuses the detector's rate-limited
    # auto-trace. Runs on EVERY host — per-host compile/recompile counters
    # feed the fleet view (events stay logging-host-only via EventLog's
    # enabled gate)
    sentry = maybe_sentry(
        cfg, telemetry=telemetry, events=events, detector=detector
    )
    spans.end(boot_span)
    events.emit(
        "run_start", entry="pretrain", epochs=epochs,
        steps_per_epoch=steps_per_epoch, global_batch=global_batch,
        pid=os.getpid(),
    )
    # resume, step builders, iterator, exporter, and the step's compilation
    # (boot/compile): ended before the first dispatch
    build_span = spans.begin("boot/build_step")
    start_epoch = 1
    skip_steps = 0
    # the PRIOR generation's topology record, read BEFORE this run
    # overwrites the sidecar below — the elastic remesh accept/reject input
    prior_topology = (
        read_topology(save_dir)
        if bool(cfg.select("experiment.resume", False)) else None
    )
    if bool(cfg.select("experiment.resume", False)):
        # newest checkpoint whose sha256 sidecar verifies; a corrupt latest
        # falls back to the previous one instead of failing the run
        t_restore = time.perf_counter()
        restored, ckpt = restore_checkpoint_with_fallback(save_dir, state)
        if restored is not None:
            state = restored
            telemetry.observe_restore(time.perf_counter() - t_restore)
            start_epoch, skip_steps = resume_point(
                int(state.step), steps_per_epoch
            )
            # cross-topology resume (elastic remesh): accepted only when the
            # global batch is preserved and the checkpoint sits on an epoch
            # boundary; anything else raises here, before any compile.
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
                    "(%d -> %d hosts), per-device batch now %d "
                    "(global batch %d preserved)",
                    topology_change["devices_before"],
                    topology_change["devices_after"],
                    topology_change["hosts_before"],
                    topology_change["hosts_after"],
                    topology_change["per_device_batch"], global_batch,
                )
            # re-seat the timeline like pretrain_results.json below: drop
            # epoch/checkpoint events this run is about to re-emit
            events.reseat(start_epoch)
            events.emit(
                "resume", epoch=start_epoch, step=int(state.step),
                skip_steps=skip_steps, checkpoint=ckpt,
            )
            logger.info(
                "Resumed from %s at epoch %d%s", ckpt, start_epoch,
                f" (skipping {skip_steps} already-consumed steps)"
                if skip_steps else "",
            )
    if is_logging_host():
        write_topology(
            save_dir,
            n_devices=jax.device_count(),
            n_processes=n_hosts,
            global_batch=global_batch,
        )

    step_kwargs = dict(
        temperature=float(cfg.parameter.temperature),
        strength=float(cfg.experiment.strength),
        negatives=str(cfg.select("loss.negatives", "global")),
        fused=bool(cfg.select("loss.fused", False)),
        forward_mode=str(cfg.select("model.forward_mode", "two_pass")),
        remat=bool(cfg.select("model.remat", False)),
        # parallel.grad_allreduce: wire format of the data-axis gradient
        # all-reduce — exact | bf16 | int8 (parallel/compress.py,
        # docs/PERF.md §"Compressed collectives")
        grad_allreduce=str(cfg.select("parallel.grad_allreduce", "exact")),
        # parallel.comm_overlap / comm_chunks: collective schedule — "chunked"
        # splits the all-reduce into N ppermute rings XLA overlaps with the
        # backward; "async" issues those rings eagerly under the staged
        # backward (docs/PERF.md §"Async overlapped backward")
        comm_overlap=comm_overlap,
        comm_chunks=comm_chunks,
        # runtime.augment_impl: xla | fused — fused runs both views through
        # the Pallas one-VMEM-pass kernel (ops/augment_pallas.py,
        # docs/PERF.md §"Fused augmentation")
        augment_impl=str(cfg.select("runtime.augment_impl", "xla")),
        # obs/compile.py recompile sentry: the builders route the jitted
        # step through an instrumented AOT lower/compile path when set
        sentry=sentry,
    )
    if crop_lengths:
        # token documents: the views are span crops packed into rows
        step_kwargs["crop_lengths"] = crop_lengths
    if n_model > 1:
        # tensor-parallel projection head over the model axis; what it
        # combines with: docs/PERF.md §"Tensor-parallel support matrix"
        from simclr_tpu.parallel.tp import make_pretrain_step_tp as build_step
    else:
        # the module's attribute as it is now: the benchmark's reference
        # replaces it to follow the first steps
        build_step = make_pretrain_step
    step_fn = build_step(model, tx, mesh, **step_kwargs)
    iterator = EpochIterator(
        dataset, global_batch, seed=seed, shuffle=True,
        sharding=batch_sharding(mesh),
        gather_threads=int(cfg.parameter.num_workers),
    )

    # live HBM accounting (obs/device.py): per-device memory_stats gauges
    # sampled at scrape time from the exporter thread — host-side allocator
    # queries, zero device syncs. Every host monitors its OWN local devices'
    # HBM — per-host watermarks are fleet gauges (the events stream stays
    # logging-host-only)
    monitor = maybe_monitor(cfg, events=events)
    if monitor is not None:
        telemetry.attach_device_monitor(monitor)

    if is_logging_host():
        os.makedirs(save_dir, exist_ok=True)
        logger.info(
            "pretrain %s: platform=%s device_kind=%r devices=%d, %d params, "
            "mesh %s, global batch %d (%d/device), "
            "%d steps/epoch, %d epochs, lr0 %.4f, negatives=%s",
            cfg.experiment.name, device["platform"], device["kind"],
            device["count"], param_count(state.params), dict(mesh.shape),
            global_batch, cfg.experiment.batches, steps_per_epoch, epochs, lr0,
            cfg.select("loss.negatives", "global"),
        )

    base_key = jax.random.key(seed + 1)
    metrics = {"loss": jnp.zeros(())}
    save_model_epoch = int(cfg.experiment.save_model_epoch)
    monitor_val_acc = None
    # per-epoch evidence curves (loss always; monitor when eval_every>0) as
    # [epoch, value] pairs — self-describing under resume, where the run
    # covers start_epoch..epochs only. Persisted to
    # <save_dir>/pretrain_results.json so a long run leaves a committable
    # learning artifact, not just a final scalar.
    loss_history: list[list[float]] = []
    monitor_history: list[list[float]] = []
    if start_epoch > 1:
        # Re-seat the persisted curves at the resume point so this run
        # appends [epoch, value] rows without duplicating restored epochs:
        # rows at or past start_epoch are about to be re-run (the resumed
        # checkpoint may be older than the last logged epoch) and are
        # dropped; everything earlier — including the epoch-0 random-init
        # probe — carries over.
        prior_path = os.path.join(save_dir, "pretrain_results.json")
        if os.path.exists(prior_path):
            try:
                with open(prior_path) as f:
                    prior = json.load(f)
            except ValueError:
                prior = {}
            loss_history = [
                r for r in prior.get("loss_history", []) if r[0] < start_epoch
            ]
            monitor_history = [
                r for r in prior.get("monitor_history", []) if r[0] < start_epoch
            ]

    def write_results(summary: dict) -> None:
        """Persist the run summary/curves; called every epoch (not just at
        the end) so a preempted or crashed run leaves its history for the
        resume to re-seat."""
        if not is_logging_host():
            return
        from simclr_tpu.utils.ioutil import atomic_write

        # every write names the devices the numbers came from, and what the
        # persistent compilation cache did for this process
        summary = {
            **summary,
            "device": device,
            "compile_cache": compilation_cache_stats(),
        }
        atomic_write(
            os.path.join(save_dir, "pretrain_results.json"),
            lambda f: json.dump(summary, f, indent=1),
        )

    # experiment.eval_every > 0: centroid-probe the test split every N
    # epochs — a REAL monitor where the reference's validation() is an
    # empty stub (/root/reference/main.py:53-58, SURVEY §2.5.6). Off by
    # default for recipe parity.
    eval_every = int(cfg.select("experiment.eval_every", 0) or 0)
    if eval_every > 0:
        test_ds = load_dataset(
            cfg.experiment.name, "test",
            data_dir=cfg.select("experiment.data_dir"),
            synthetic_ok=bool(cfg.select("experiment.synthetic_data", False)),
            synthetic_size=cfg.select("experiment.synthetic_size"),
            synthetic_noise=cfg.select("experiment.synthetic_noise"),
        )
        # on-device reshard to replicated: the encode program expects
        # replicated variables, and a TP run's live head leaves are
        # model-sharded global arrays that span non-addressable devices
        # under multi-process (a bare host fetch would raise). The jitted
        # identity's out_shardings makes XLA do the all-gather; the
        # replicated outputs feed the encode jit directly — no host round
        # trip.
        gather_replicated = jax.jit(
            lambda t: t, out_shardings=replicated_sharding(mesh)
        )
        # the shared f32 extraction model — the monitor's accuracy is
        # directly comparable to a post-hoc eval.py centroid run on the
        # same checkpoint regardless of the training compute dtype
        from simclr_tpu.eval import build_eval_model, centroid_probe, extract_features

        monitor_model = build_eval_model(cfg)

        def run_monitor_probe(epoch: int) -> float:
            variables = gather_replicated(
                {"params": state.params, "batch_stats": state.batch_stats}
            )
            train_X = extract_features(
                monitor_model, variables, dataset.images, mesh, global_batch, False
            )
            val_X = extract_features(
                monitor_model, variables, test_ds.images, mesh, global_batch, False
            )
            res = centroid_probe(
                train_X, dataset.labels, val_X, test_ds.labels,
                dataset.num_classes, top_k=5,
            )
            telemetry.observe_val_acc(res["val_acc"])
            if is_logging_host():
                logger.info(
                    "Epoch:%d centroid probe: val top-1 %.4f (top-5 %.4f)",
                    epoch, res["val_acc"], res["val_top_5_acc"],
                )
            return res["val_acc"]
    if eval_every > 0 and start_epoch == 1 and not monitor_history:
        # epoch-0 probe: the RANDOM-INIT accuracy anchors the monitor curve,
        # so a later reader can tell learned features from data that is
        # already separable to an untrained encoder (skipped when a re-seated
        # history already carries it)
        monitor_history.append([0, run_monitor_probe(0)])
    # host-side step counter: reading state.step off-device every iteration
    # would sync the host to the in-flight step and kill async dispatch
    cur_step = (start_epoch - 1) * steps_per_epoch + skip_steps
    # steady-state trace window: skips the first (compiling) step
    tracer = StepTraceWindow(
        cfg.select("experiment.profile_dir"),
        start=cur_step + 2,
        length=int(cfg.select("experiment.profile_steps", 10) or 10),
        enabled=is_logging_host(),
    )
    t_start = time.time()
    # steady-state throughput, excluding the first (compiling) steps; the
    # per-epoch log line reports the cumulative rate instead
    timer = StepTimer(global_batch)
    stem = str(cfg.experiment.output_model_name)
    # per-host /metrics + /debug/trace exporter; None unless telemetry.port
    # (or telemetry.ready_file for an ephemeral port) is configured. Every
    # process runs one — process i>0 publishes telemetry.p<i>.ready — so
    # the supervisor's FleetCollector sees the whole fleet
    exporter = maybe_start_exporter(
        cfg, telemetry, save_dir, process_index=jax.process_index()
    )
    spans_file = spans_path(save_dir, jax.process_index())
    # the open `boundary` span: from the step loop's exit to the next
    # epoch's first batch in hand, or to the results file where that batch
    # and its step went out before the boundary opened
    boundary_span = None
    # the loop's one-step lead over a boundary: the next epoch's
    # batches, first step dispatched, with its start step and start time
    led = None

    def flush_spans() -> None:
        try:
            spans.flush(spans_file, process_index=jax.process_index())
        except OSError as e:  # a full disk must not change how the run ends
            logger.warning("spans not written to %s: %s", spans_file, e)

    def end_boundary() -> None:
        nonlocal boundary_span
        spans.end(boundary_span)
        boundary_span = None

    def timed_save(path: str) -> None:
        t_save = time.perf_counter()
        save_checkpoint(path, state)  # checkpoint/save, /digest, /sidecar
        telemetry.observe_save(time.perf_counter() - t_save)

    def open_epoch(at_epoch: int, skip: int = 0):
        """The epoch's prefetched batches and the first of them (``None`` if
        it has none), waited for in ``boundary/first_batch``."""
        batches = iterator.batches(at_epoch)
        if skip:
            # mid-epoch resume: replay the epoch's deterministic batch order
            # past the consumed prefix; step RNG folds on the absolute
            # cur_step, so the continuation is exact
            batches = itertools.islice(batches, skip, None)
        batches = prefetch(batches)
        with spans.span("boundary/first_batch", epoch=at_epoch):
            return batches, next(batches, None)

    def train_step(batch, at_epoch: int) -> None:
        """One step of the loop: dispatch, tick, beat."""
        nonlocal state, metrics, cur_step
        tracer.tick(cur_step, pending=metrics["loss"])
        # the key's fold_in is a device program too, and the first of the
        # two to meet the run-ahead limit: in steady state this span is the
        # wait for a free slot
        with spans.span("dispatch"):
            step_rng = jax.random.fold_in(base_key, cur_step)
            state, metrics = step_fn(state, batch["image"], step_rng)
        timer.tick(metrics["loss"])
        cur_step += 1
        if detector is not None:
            # BEFORE the beat: the beat is where fault injection wedges, and
            # the watchdog must already be armed to catch exactly that class
            # of hang
            detector.tick(cur_step, at_epoch)
        with spans.span("beat"):
            guard.beat(cur_step, at_epoch)

    def leave_preempted(path: str, at_epoch: int):
        """The tail of a preemption, inside its ``preempt`` span: event,
        last beat, the recorder's flush (inside ``preempt_s``, so timed)."""
        with spans.span("preempt/event"):
            events.emit(
                "preempt", step=cur_step, epoch=at_epoch, checkpoint=path
            )
        with spans.span("preempt/beat"):
            guard.beat_preempted(cur_step, at_epoch)
        with spans.span("preempt/flush"):
            flush_spans()
        raise PreemptedRun(path)

    guard.install_signals()
    try:
        epoch = start_epoch
        while epoch <= epochs:
            if led is None:
                epoch_start_step, epoch_t0 = cur_step, time.perf_counter()
                batches, batch = open_epoch(epoch, skip_steps)
                skip_steps = 0
                end_boundary()
                if batch is not None and build_span is not None:
                    # boot/build_step ends before the run's first dispatch,
                    # the step's compilation (the sentry's boot/compile)
                    # still inside it
                    if hasattr(step_fn, "compile"):  # a WatchedFunction
                        step_fn.compile(
                            state, batch["image"],
                            jax.random.fold_in(base_key, cur_step),
                        )
                    spans.end(build_span)
                    build_span = None
            else:
                # the epoch's first step went out before the last
                # boundary. A preemption seen since (in that step's beat,
                # or while the host sat in the fetch) checkpoints behind
                # that one step, below, before a second is dispatched
                (batches, epoch_start_step, epoch_t0), led = led, None
                batch = None if guard.preempt_requested else next(batches, None)
            with spans.span("epoch", epoch=epoch, step=cur_step + 1) as epoch_span:
                while batch is not None:
                    train_step(batch, epoch)
                    if guard.preempt_requested:
                        break
                    epoch_span.step = cur_step + 1  # what the next spans serve
                    batch = next(batches, None)
            if detector is not None:
                # epoch-boundary work (probe, checkpoint I/O, preempt saves)
                # is not a step: disarm so it can never read as a stall, and
                # keep its duration out of the step-time window
                detector.pause()
            if guard.preempt_requested:
                # land a resumable checkpoint at this step boundary, then
                # exit 75 via main() — at an exact epoch boundary this is the
                # regular boundary checkpoint; mid-epoch it gets "-preempt"
                seen_ns = time.monotonic_ns()
                with spans.span("preempt", epoch=epoch, step=cur_step) as pre:
                    spans.add(
                        "preempt/notice", guard.preempt_ns or seen_ns, seen_ns,
                        parent=pre.id, epoch=epoch, step=cur_step,
                    )
                    with spans.span("checkpoint"):
                        with spans.span("checkpoint/drain"):
                            timer.pause(metrics["loss"])
                            epoch_loss = float(metrics["loss"])
                        if (
                            cur_step == epoch * steps_per_epoch
                            and math.isfinite(epoch_loss)
                            and (not loss_history or loss_history[-1][0] < epoch)
                        ):
                            # the preempt landed on a completed epoch (elastic
                            # grow-back drains SIGTERM at exactly this
                            # boundary): its loss row and epoch event are in
                            # hand — persist them, or the resumed run's
                            # history skips this epoch
                            loss_history.append([epoch, epoch_loss])
                            events.emit(
                                "epoch", epoch=epoch, step=cur_step,
                                loss=epoch_loss,
                                seconds=round(time.perf_counter() - epoch_t0, 6),
                                **expert_counters(metrics),
                            )
                            write_results(
                                {
                                    "epochs": epochs,
                                    "save_dir": save_dir,
                                    "loss_history": loss_history,
                                    "monitor_history": monitor_history,
                                    "complete": False,
                                }
                            )
                        path = os.path.join(
                            save_dir,
                            preempt_checkpoint_name(cur_step, steps_per_epoch, stem),
                        )
                        timed_save(path)
                    leave_preempted(path, epoch)

            # the epoch's end, as the boundary below reports it; cur_step and
            # metrics move on by one where the boundary is led
            end_step, last_loss, last_metrics = cur_step, metrics["loss"], metrics
            # the learning rate the boundary reports is a handful of tiny
            # device programs (the eager optax schedule). Queued here, behind
            # the epoch's last step, it is in hand with the loss; evaluated
            # after the fetch it would queue behind a led step, and the host
            # would sit out that step in the middle of the boundary
            lr_pending = schedule(max(end_step - 1, 0))
            if leads_over_boundary(
                epoch, epochs=epochs, save_model_epoch=save_model_epoch,
                eval_every=eval_every,
            ):
                # a one-step lead: the next epoch's first batch is taken and
                # its first step dispatched BEFORE this epoch's loss is
                # fetched, so the device runs that step while the host does
                # the boundary's work (fetch wake-up, telemetry, log, event,
                # results) instead of standing still for it. Same key, same
                # batch, same order as without the lead; last_loss is an
                # output of the finished step, not donated
                next_t0 = time.perf_counter()
                batches, batch = open_epoch(epoch + 1)
                if batch is None:
                    batches.close()
                else:
                    with spans.span("epoch", epoch=epoch + 1, step=cur_step + 1):
                        train_step(batch, epoch + 1)
                    led = (batches, end_step, next_t0)
                    if detector is not None:
                        detector.pause()  # the led step's tick armed it again
            # n: the steps of the next epoch dispatched before the fetch
            boundary_span = spans.begin(
                "boundary", epoch=epoch, step=end_step, n=int(led is not None)
            )
            with spans.span("boundary/loss_fetch"):
                epoch_loss = guard.checked_loss(end_step, float(last_loss))
            # epoch telemetry BEFORE the boundary beat, so the beat's
            # snapshot (and any scrape) reflects the epoch that just
            # finished; every input is a host float already in hand, and
            # every host updates its OWN gauges for the fleet view
            with spans.span("boundary/telemetry"):
                lr_now = float(lr_pending)
                telemetry.observe_epoch(
                    epoch,
                    epochs=epochs,
                    step=end_step,
                    steps=end_step - epoch_start_step,
                    seconds=time.perf_counter() - epoch_t0,
                    loss=epoch_loss,
                    lr=lr_now,
                )
            with spans.span("beat"):
                guard.beat(end_step, epoch, loss=epoch_loss)
            if not math.isfinite(epoch_loss):
                # roll back to the newest verified checkpoint; a different
                # RNG stream on the retry — deterministically replaying the
                # same trajectory would reproduce the same divergence
                try:
                    t_restore = time.perf_counter()
                    restored, rpath = restore_checkpoint_with_fallback(
                        save_dir, state
                    )
                except CheckpointCorruptionError as e:
                    raise PoisonedRun(str(e)) from e
                guard.record_rollback(epoch_loss, rpath)
                telemetry.observe_restore(time.perf_counter() - t_restore)
                if led is not None:
                    # the led step goes with the state it produced
                    led[0].close()
                    led = None
                state = restored
                cur_step = int(state.step)
                epoch, skip_steps = resume_point(cur_step, steps_per_epoch)
                loss_history = [r for r in loss_history if r[0] < epoch]
                monitor_history = [r for r in monitor_history if r[0] < epoch]
                # the rolled-back epochs re-run: re-seat the timeline too so
                # their epoch/checkpoint events are not duplicated
                events.reseat(epoch)
                base_key = jax.random.fold_in(
                    jax.random.key(seed + 1), guard.nan_rollbacks
                )
                continue
            if is_logging_host():
                # one line per epoch, the reference's rank-0 log (main.py:124-127)
                with spans.span("boundary/log"):
                    imgs_per_sec = (
                        (end_step - (start_epoch - 1) * steps_per_epoch)
                        * global_batch / max(time.time() - t_start, 1e-9)
                    )
                    logger.info(
                        "Epoch:%d/%d progress:%.3f loss:%.3f, lr:%.7f, "
                        "imgs/sec:%.0f",
                        epoch, epochs, epoch / epochs, epoch_loss, lr_now,
                        imgs_per_sec,
                    )
            loss_history.append([epoch, epoch_loss])
            with spans.span("boundary/event"):
                events.emit(
                    "epoch", epoch=epoch, step=end_step, loss=epoch_loss,
                    seconds=round(time.perf_counter() - epoch_t0, 6),
                    **expert_counters(last_metrics),
                )
            if eval_every > 0 and (epoch % eval_every == 0 or epoch == epochs):
                with spans.span("boundary/probe"):
                    timer.pause(metrics["loss"])  # keep probe compute out of imgs/sec
                    monitor_val_acc = run_monitor_probe(epoch)
                    monitor_history.append([epoch, monitor_val_acc])
                    timer.resume()
            if epoch % save_model_epoch == 0 or epoch == epochs:
                path = os.path.join(save_dir, checkpoint_name(epoch, stem))
                with spans.span("checkpoint"):
                    with spans.span("checkpoint/drain"):
                        # keep save I/O out of the imgs/sec window
                        timer.pause(metrics["loss"])
                    timed_save(path)
                events.emit("checkpoint", epoch=epoch, path=path)
                guard.after_save(epoch, path)
                flush_spans()  # the timer is paused for I/O already
                timer.resume()
            with spans.span("boundary/results"):
                write_results(
                    {
                        "epochs": epochs,
                        "save_dir": save_dir,
                        "loss_history": loss_history,
                        "monitor_history": monitor_history,
                        "complete": False,
                    }
                )
            if led is not None:
                end_boundary()  # the next epoch's first batch and step are out
            epoch += 1
        end_boundary()
    except Exception as exc:
        # an allocator RESOURCE_EXHAUSTED leaves its forensic behind —
        # device memory profile + oom event — before the error propagates;
        # any other exception passes through untouched
        if is_logging_host():
            maybe_dump_oom_profile(save_dir, exc, events=events)
        raise
    finally:
        guard.restore_signals()
        if detector is not None:
            detector.close()
        if exporter is not None:
            exporter.close()
        # on a normal end, a preemption, a poisoned run and any exception:
        # what is still open is written as open, which says where it stopped
        flush_spans()
        spans.reset()

    tracer.close(pending=metrics["loss"])
    throughput = timer.summary()
    if is_logging_host() and throughput["steps"] > 0:
        logger.info(
            "steady-state: %.0f imgs/sec (%.0f per chip) over %d steps",
            throughput["imgs_per_sec"], throughput["imgs_per_sec_per_chip"],
            throughput["steps"],
        )
    summary = {
        "final_loss": float(metrics["loss"]),
        "steps": int(state.step),
        "epochs": epochs,
        "save_dir": save_dir,
        "global_batch": global_batch,
        "n_data_shards": n_data,
        "device": device,
        "lr0": lr0,
        "imgs_per_sec_steady": throughput["imgs_per_sec"],
    }
    summary["loss_history"] = loss_history
    summary["complete"] = True
    if monitor_history:
        summary["monitor_history"] = monitor_history
    if monitor_val_acc is None and monitor_history:
        # resumed with nothing left to run: the last re-seated probe stands
        monitor_val_acc = monitor_history[-1][1]
    if monitor_val_acc is not None:
        summary["monitor_val_acc"] = monitor_val_acc
    write_results(summary)
    events.emit("run_end", step=int(state.step), loss=summary["final_loss"])
    return summary


def main(argv: list[str] | None = None):
    from simclr_tpu.config import run_multirun, split_multirun_flag
    from simclr_tpu.parallel.multihost import maybe_initialize_multihost
    from simclr_tpu.supervisor.guard import EXIT_POISONED, EXIT_PREEMPTED
    from simclr_tpu.utils.platform import configure_compilation_cache

    # boot/runtime: from here to the backend's start in run_pretrain
    recorder().reset()
    recorder().begin("boot/runtime")
    configure_compilation_cache()
    maybe_initialize_multihost()
    multirun, args = split_multirun_flag(list(sys.argv[1:] if argv is None else argv))
    # exit-code contract (docs/FAULT_TOLERANCE.md): 75 = preempted but
    # resumable (the supervisor restarts with resume=true), 76 = poisoned
    # (restarting cannot help; the supervisor gives up)
    try:
        if multirun:
            return run_multirun(run_pretrain, "config", args)
        cfg = load_config("config", overrides=args)
        return run_pretrain(cfg)
    except PreemptedRun as e:
        logger.info("%s", e)
        sys.exit(EXIT_PREEMPTED)
    except PoisonedRun as e:
        logger.error("%s", e)
        sys.exit(EXIT_POISONED)


if __name__ == "__main__":
    main()
