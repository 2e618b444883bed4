"""Compiled SPMD train/eval steps (shard_map + jit).

One jitted program per entry-point hot loop, replacing the reference's
eager-loop-plus-DDP structure (``/root/reference/main.py:104-122``,
``supervised.py:109-139``). Each step consumes and returns the full
:class:`~simclr_tpu.parallel.train_state.TrainState` (donated) and runs under
``jax.shard_map`` over the (data, model) mesh so every collective is explicit:

  * gradients:   ``psum`` over the data axis (the reference's DDP bucketed
                 all-reduce, ``main.py:178``);
  * BatchNorm:   ``pmean`` of batch statistics inside the model's forward
                 (the reference's SyncBN, ``main.py:176``);
  * NT-Xent:     per ``loss.negatives`` — ``all_gather`` of embeddings for
                 global negatives (the TPU scaling axis, SURVEY §5.7) or the
                 reference's local-batch semantics (``loss.py:25-36``);
  * metrics:     ``psum`` of sums/corrects (the reference's explicit
                 ``dist.reduce`` in ``supervised.py:137-139``).

Augmentation runs ON DEVICE inside the same program (per-example PRNG keys
folded with the device's data-axis index), so the host feeds raw uint8 and
the whole step — augment, two forwards, loss, backward, LARS update — is one
XLA computation with no host round-trips.

Gradient math note: the loss functions return the GLOBAL mean loss (identical
on every replica, collectives included), so per-replica autodiff yields each
replica's contribution d(global loss)/d(params-via-local-batch); the ``psum``
over the data axis then assembles the exact full gradient. This holds for
both the gathered-negatives and local-negatives objectives.
"""

from __future__ import annotations

import functools
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from simclr_tpu.data.augment import simclr_augment_single, to_float
from simclr_tpu.obs.trace import (
    SCOPE_AUGMENT,
    SCOPE_ENCODER,
    SCOPE_GRAD_REDUCE,
    SCOPE_LARS,
    SCOPE_NTXENT,
)
from simclr_tpu.ops.augment_pallas import (
    fused_one_view,
    fused_two_views,
    validate_impl as validate_augment_impl,
)
from simclr_tpu.ops.ntxent import (
    ntxent_loss_local_negatives,
    ntxent_loss_sharded_rows,
)
from simclr_tpu.ops.ntxent_pallas import (
    ntxent_loss_fused,
    ntxent_loss_fused_sharded,
)
from simclr_tpu.ops.ntxent_ring import ntxent_loss_ring
from simclr_tpu.parallel import compress
from simclr_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from simclr_tpu.parallel.train_state import TrainState

Metrics = dict[str, jnp.ndarray]

_REP = P()          # replicated
_BATCH = P(DATA_AXIS)  # batch dim sharded over the data axis

RESIDENCIES = ("replicated", "sharded")

# fraction of one chip's HBM the resident dataset may claim under
# epoch_compile — the rest belongs to params/optimizer state/activations
# (the step's working set; ~8.2 GB of HBM traffic at batch 512, PERF.md)
DATASET_HBM_FRACTION = 0.5


def device_hbm_budget_bytes():
    """Spare-HBM budget for on-device dataset residency, or None if unknown.

    ``memory_stats`` is backend-dependent: TPU/GPU report ``bytes_limit``;
    CPU test meshes report nothing (or raise), in which case the preflight
    skips the capacity check rather than guessing. All key access goes
    through the hardened sampler in ``obs/device.py`` — the same one the
    live HBM gauges use — so a partial or exotic stats payload degrades to
    "unknown budget", never a KeyError.
    """
    from simclr_tpu.obs.device import sample_memory_stats

    try:
        device = jax.local_devices()[0]
    except Exception:  # pragma: no cover — backend-dependent API
        return None
    stats = sample_memory_stats(device)
    if not stats or not stats.get("bytes_limit"):
        return None
    return int(stats["bytes_limit"] * DATASET_HBM_FRACTION)


def _watch(jit_fn, sentry, name: str, *, steps_from_args=None):
    """Route a jitted step through the compile sentry's explicit AOT
    lower/compile path (``obs/compile.py``) so every compilation — and any
    post-warmup recompilation — is timed, fingerprinted, and cost-analyzed.
    The bare jit dispatch is returned unchanged when observability is off.
    """
    if sentry is None:
        return jit_fn
    return sentry.watch(jit_fn, name, steps_from_args=steps_from_args)


def _epoch_steps_from_args(n_arrays: int):
    """Steps-per-call extractor for epoch programs: the scan length is
    ``idx_epoch.shape[0]`` (args are ``(state, *arrays, idx_epoch,
    base_key, step0)``), letting the sentry normalize the whole-epoch XLA
    cost back to per-step numbers comparable with the roofline model."""

    def steps(args):
        return int(args[1 + n_arrays].shape[0])

    return steps


def superepoch_steps_from_args(idx_pos: int):
    """Steps-per-call extractor for SUPEREPOCH programs: the stacked index
    tensor at ``args[idx_pos]`` is ``(K, steps_per_epoch, global_batch)``,
    so one call covers ``K * steps_per_epoch`` optimizer steps — the number
    the sentry divides the whole-program XLA cost by."""

    def steps(args):
        idx = args[idx_pos]
        return int(idx.shape[0] * idx.shape[1])

    return steps


def check_epoch_compile_preconditions(
    n_samples: int,
    global_batch: int,
    profile_dir=None,
    *,
    dataset_bytes: int | None = None,
    n_data_shards: int = 1,
    residency: str = "replicated",
    hbm_budget_bytes: int | None = None,
    epochs_per_compile: int = 1,
    steps_per_epoch: int | None = None,
    probe_bytes: int | None = None,
    probe_samples: int = 0,
):
    """Shared ``runtime.epoch_compile`` preflight for the entry points.

    The epoch-compiled path keeps the whole dataset resident in HBM and has
    no per-step host boundary, so it cannot bracket a profiler trace window
    around individual steps. Raising here (rather than per entry point)
    keeps ``main.py`` and ``supervised.py`` in lockstep.

    HBM capacity math (``runtime.dataset_residency``): with ``replicated``
    residency every chip holds all ``dataset_bytes``; with ``sharded``
    residency each data-axis shard holds only its contiguous
    ``ceil(n_samples / n_data_shards)`` row block (``mesh.put_row_sharded``),
    so the per-chip footprint divides by the data-axis size. The check
    compares that footprint against ``hbm_budget_bytes`` (defaulting to
    :func:`device_hbm_budget_bytes`; unknown budget — e.g. the CPU test
    mesh — skips the check). A replicated dataset that would fit sharded
    fails with the fix spelled out instead of a bare rejection.

    Multi-host runs are supported: every process loads the same dataset and
    derives identical index matrices from the shared seed; the dataset
    upload goes through ``mesh.put_replicated`` (cross-process equality
    check) or ``mesh.put_row_sharded`` (each process fills only the shards
    it addresses). Exercised by real 2-process launches in
    tests/test_launch.py.

    Superepochs (``runtime.epochs_per_compile=K > 1``) grow the resident
    footprint in two accounted ways: the index tensor is ``K`` stacked epoch
    matrices (``K * steps_per_epoch * global_batch`` int32, replicated on
    every chip), and the in-program ``eval_every`` monitor keeps the test
    split resident too (``probe_bytes`` over ``probe_samples`` rows, laid
    out per the same ``residency``). Both are added to the per-chip total
    before the budget comparison.

    Returns the per-chip resident bytes (dataset + probe split + index
    tensors; None when the dataset size is unknown).
    """
    if epochs_per_compile < 1:
        raise ValueError(
            f"epochs_per_compile must be >= 1, got {epochs_per_compile}"
        )
    if n_samples < global_batch:
        # the per-step path raises this inside EpochIterator; here it would
        # otherwise run a zero-length scan and checkpoint untrained params
        raise ValueError(
            f"dataset of {n_samples} samples smaller than global batch "
            f"{global_batch}"
        )
    if residency not in RESIDENCIES:
        raise ValueError(
            f"dataset_residency must be one of {RESIDENCIES}, got {residency!r}"
        )
    resident_bytes = None
    if dataset_bytes is not None and n_samples > 0:
        bytes_per_row = dataset_bytes / n_samples
        rows_resident = (
            n_samples
            if residency == "replicated"
            else -(-n_samples // max(n_data_shards, 1))
        )
        resident_bytes = int(rows_resident * bytes_per_row)
        if probe_bytes is not None and probe_samples > 0:
            # the in-program monitor's resident test split follows the same
            # residency layout as the train set
            probe_rows = (
                probe_samples
                if residency == "replicated"
                else -(-probe_samples // max(n_data_shards, 1))
            )
            resident_bytes += int(probe_rows * (probe_bytes / probe_samples))
        if steps_per_epoch:
            # the K-epoch program's stacked index tensor, replicated per chip
            resident_bytes += int(
                epochs_per_compile * steps_per_epoch * global_batch * 4
            )
        budget = (
            device_hbm_budget_bytes()
            if hbm_budget_bytes is None
            else hbm_budget_bytes
        )
        if budget is not None and resident_bytes > budget:
            sharded_bytes = int(-(-n_samples // max(n_data_shards, 1)) * bytes_per_row)
            hint = (
                f"; runtime.dataset_residency=sharded would hold only "
                f"{sharded_bytes / 2**20:.0f} MiB per chip "
                f"({n_data_shards} data shards) and fits this budget"
                if residency == "replicated" and sharded_bytes <= budget
                else ""
            )
            raise ValueError(
                f"epoch_compile dataset residency of "
                f"{resident_bytes / 2**20:.0f} MiB per chip ({residency}) "
                f"exceeds the {budget / 2**20:.0f} MiB HBM budget{hint}"
            )
    if profile_dir:
        from simclr_tpu.utils.logging import get_logger

        get_logger().warning(
            "experiment.profile_dir is ignored with runtime.epoch_compile "
            "(no per-step host boundary to bracket a trace window)"
        )
    return resident_bytes


def _global_sample_keys(rng, n_local: int, views: int = 2):
    """Per-sample augmentation keys indexed by GLOBAL batch position.

    ``key[v, i] = fold_in(rng, v * N + shard * n_local + i)`` where ``N`` is
    the global batch — a pure function of the sample's position in the
    global batch and the view index, NOT of the device layout. An elastic
    remesh that rescales ``n_local`` while preserving the global batch
    (supervisor/elastic.py) therefore draws bit-identical augmentation
    parameters for every sample, and a resumed trajectory tracks an
    uninterrupted run to within float reduction-order noise. Returned flat
    ``(views * n_local,)`` key array is view-major — this shard's view-0
    keys first — matching the ``split(rng, views * n)`` consumption layout.
    Must run inside the data-axis ``shard_map``.
    """
    n_global = n_local * jax.lax.axis_size(DATA_AXIS)
    rows = jax.lax.axis_index(DATA_AXIS) * n_local + jnp.arange(
        n_local, dtype=jnp.int32
    )
    idx = (
        jnp.arange(views, dtype=jnp.int32)[:, None] * n_global + rows[None, :]
    ).reshape(-1)
    return jax.vmap(lambda i: jax.random.fold_in(rng, i))(idx)


def _augment_two_views(
    rng, images, strength, out_size, augment_impl="xla", keys=None
):
    """Two on-device SimCLR views of the local uint8 shard.

    ``augment_impl="xla"`` is the vmapped per-example chain, converting
    uint8→f32 once per IMAGE (hoisted out of ``simclr_augment_single``, not
    paid per view); ``"fused"`` routes through the Pallas one-VMEM-pass
    kernel (``ops/augment_pallas.py``), which dequantizes in-VMEM and emits
    both views from one read of the uint8 tile. Both impls consume the same
    key schedule (``split(rng, 2n)``, first half view 0) and the same
    samplers, so equal seeds draw bit-identical augmentation parameters.
    The training step passes ``keys`` precomputed by
    :func:`_global_sample_keys` (same (2n,) layout) so the draw is
    layout-invariant; ``rng`` is ignored then.
    """
    if augment_impl == "fused":
        return fused_two_views(rng, images, strength, out_size, keys=keys)
    images = to_float(images)
    n = images.shape[0]
    if keys is None:
        keys = jax.random.split(rng, 2 * n)
    aug = jax.vmap(simclr_augment_single, in_axes=(0, 0, None, None))
    return aug(keys[:n], images, strength, out_size), aug(keys[n:], images, strength, out_size)


def _forward_fn(model, remat: bool):
    """Mutable-BN training forward, optionally rematerialized.

    ``remat=True`` wraps the forward in ``jax.checkpoint``: activations are
    recomputed during the backward pass instead of stored, trading ~1/3 more
    FLOPs for O(depth) less HBM — the enabler for very large per-chip
    batches (``model.remat`` config).
    """

    def fwd(params, batch_stats, v):
        return model.apply(
            {"params": params, "batch_stats": batch_stats}, v, train=True,
            mutable=["batch_stats"],
        )

    return jax.checkpoint(fwd) if remat else fwd


def _apply_two_pass(fwd, params, batch_stats, v0, v1):
    """Two sequential forwards threading BN running stats.

    Matches the reference's per-view forwards (``main.py:112-113``): each
    view's batch forms its own BN batch statistics and the running stats get
    two momentum updates per step — NOT one concatenated 2B forward.
    """
    z0, mut = fwd(params, batch_stats, v0)
    z1, mut = fwd(params, mut["batch_stats"], v1)
    return z0, z1, mut["batch_stats"]


def _apply_concat(fwd, params, batch_stats, v0, v1):
    """One forward over the concatenated 2B batch (performance option).

    Halves kernel-launch/weight-streaming overhead by doubling every matmul's
    batch, at the cost of BN statistics spanning both views jointly (the
    google-research SimCLR formulation) instead of per-view — a documented
    semantic deviation behind ``model.forward_mode=concat``.
    """
    n = v0.shape[0]
    z, mut = fwd(params, batch_stats, jnp.concatenate([v0, v1], axis=0))
    return z[:n], z[n:], mut["batch_stats"]


def _ntxent(z0, z1, negatives: str, fused: bool, temperature: float):
    """The configured NT-Xent variant on this data shard's embeddings (the
    one dispatch the dp and tp steps share)."""
    if fused and negatives == "global":
        return ntxent_loss_fused_sharded(z0, z1, DATA_AXIS, temperature)
    if fused:  # local negatives, per-shard fused kernel
        return jax.lax.pmean(ntxent_loss_fused(z0, z1, temperature), DATA_AXIS)
    if negatives == "global":
        return ntxent_loss_sharded_rows(z0, z1, DATA_AXIS, temperature)
    if negatives == "ring":
        return ntxent_loss_ring(z0, z1, DATA_AXIS, temperature)
    return ntxent_loss_local_negatives(z0, z1, DATA_AXIS, temperature)


def _make_local_pretrain_step(
    model,
    tx: optax.GradientTransformation,
    *,
    temperature: float,
    strength: float,
    negatives: str,
    fused: bool,
    forward_mode: str,
    remat: bool,
    out_size: int,
    grad_allreduce: str = "exact",
    comm_overlap: str = "off",
    comm_chunks: int = compress.DEFAULT_COMM_CHUNKS,
    augment_impl: str = "xla",
):
    """The per-replica contrastive step, shared verbatim by the
    dispatch-per-step (:func:`make_pretrain_step`) and epoch-compiled
    (:func:`make_pretrain_epoch_fn`) paths so their numerics can never
    diverge.

    ``grad_allreduce`` selects the gradient all-reduce wire format
    (``parallel/compress.py``): ``exact`` is the plain fp32 psum; ``bf16``
    and ``int8`` compress the data-axis collective. Compression happens
    BEFORE ``tx.update`` — quantize-before-LARS — so every replica feeds the
    optimizer the identical dequantized gradient. ``comm_overlap``/
    ``comm_chunks`` pick the collective schedule: ``chunked`` decomposes the
    all-reduce into independent ppermute rings XLA can overlap with the
    backward's tail compute; ``async`` additionally stages the backward as an
    explicit VJP and assembles each ring's bucket from only the leaves it
    spans, so tail buckets' rings issue while head layers' backward matmuls
    are still running (same dequantized gradient as ``chunked``, bitwise
    under int8); ``off`` is bitwise-identical to the single-shot path.
    """
    compress.validate_mode(grad_allreduce)
    compress.validate_overlap(comm_overlap, comm_chunks)
    validate_augment_impl(augment_impl)
    if negatives not in ("global", "local", "ring"):
        raise ValueError(f"negatives must be global|local|ring, got {negatives!r}")
    if forward_mode not in ("two_pass", "concat"):
        raise ValueError(
            f"forward_mode must be two_pass|concat, got {forward_mode!r}"
        )
    apply_views = _apply_two_pass if forward_mode == "two_pass" else _apply_concat
    forward = _forward_fn(model, remat)
    if fused and negatives == "ring":
        raise ValueError(
            "loss.fused does not combine with negatives='ring' (the ring loss "
            "is already blockwise); use negatives='global' with fused"
        )

    def local_step(state: TrainState, images: jnp.ndarray, rng: jax.Array):
        # augmentation keys are global-batch-position-indexed (layout
        # invariant across an elastic remesh); the quantization stream
        # below stays per-shard via the shard-folded rng
        with jax.named_scope(SCOPE_AUGMENT):
            keys = _global_sample_keys(rng, images.shape[0], views=2)
            rng = jax.random.fold_in(rng, jax.lax.axis_index(DATA_AXIS))
            v0, v1 = _augment_two_views(
                rng, images, strength, out_size, augment_impl, keys=keys
            )

        def loss_fn(params):
            with jax.named_scope(SCOPE_ENCODER):
                z0, z1, new_stats = apply_views(
                    forward, params, state.batch_stats, v0, v1
                )
            with jax.named_scope(SCOPE_NTXENT):
                loss = _ntxent(z0, z1, negatives, fused, temperature)
            return loss, new_stats

        if comm_overlap == "async":
            # staged backward: explicit VJP makes the cotangent pytree a
            # first-class value whose leaves the scheduler sees individually;
            # paired with grad_allreduce's per-bucket assembly (no global
            # concatenate) each ring depends only on the leaves it spans, so
            # its hops can issue while earlier layers' backward matmuls run
            loss, vjp_fn, new_stats = jax.vjp(loss_fn, state.params, has_aux=True)
            grads, = vjp_fn(jnp.ones_like(loss))
        else:
            (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        # the quantization stream forks off the same per-step, per-data-shard
        # rng the augmentations use (fold_in is the jax stream-split idiom)
        with jax.named_scope(SCOPE_GRAD_REDUCE):
            grads = compress.grad_allreduce(
                grads, DATA_AXIS, grad_allreduce,
                key=jax.random.fold_in(rng, compress.KEY_FOLD_QUANT),
                overlap=comm_overlap, chunks=comm_chunks,
            )
        with jax.named_scope(SCOPE_LARS):
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1, params=params, batch_stats=new_stats, opt_state=new_opt
        )
        metrics = {"loss": loss}
        return new_state, metrics

    return local_step


def make_pretrain_step(
    model,
    tx: optax.GradientTransformation,
    mesh,
    *,
    temperature: float = 0.5,
    strength: float = 0.5,
    negatives: str = "global",
    fused: bool = False,
    forward_mode: str = "two_pass",
    remat: bool = False,
    out_size: int = 32,
    grad_allreduce: str = "exact",
    comm_overlap: str = "off",
    comm_chunks: int = compress.DEFAULT_COMM_CHUNKS,
    augment_impl: str = "xla",
    sentry=None,
) -> Callable[[TrainState, jax.Array, jax.Array], tuple[TrainState, Metrics]]:
    """Build the jitted contrastive train step.

    Returned callable: ``(state, images_u8, rng) -> (state, metrics)`` with
    ``images`` the raw uint8 global batch sharded over the data axis. The
    model must be constructed with ``bn_cross_replica_axis=DATA_AXIS``.

    ``fused=True`` routes the loss through the Pallas blockwise kernels
    (``ops/ntxent_pallas.py``), which never materialize the similarity
    matrix — worthwhile at large (global) batches. Supported with ``local``
    negatives (per-shard kernel) and ``global`` negatives (local anchors
    against the all-gathered candidate set); ``ring`` IS the streaming
    formulation already and has no fused variant.
    """
    local_step = _make_local_pretrain_step(
        model, tx,
        temperature=temperature, strength=strength, negatives=negatives,
        fused=fused, forward_mode=forward_mode, remat=remat, out_size=out_size,
        grad_allreduce=grad_allreduce,
        comm_overlap=comm_overlap, comm_chunks=comm_chunks,
        augment_impl=augment_impl,
    )
    sharded = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(_REP, _BATCH, _REP),
        out_specs=_REP,
        check_vma=False,
    )
    return _watch(
        jax.jit(sharded, donate_argnums=(0,)), sentry, "pretrain_step"
    )


def make_pretrain_epoch_fn(
    model,
    tx: optax.GradientTransformation,
    mesh,
    *,
    temperature: float = 0.5,
    strength: float = 0.5,
    negatives: str = "global",
    fused: bool = False,
    forward_mode: str = "two_pass",
    remat: bool = False,
    out_size: int = 32,
    residency: str = "replicated",
    grad_allreduce: str = "exact",
    comm_overlap: str = "off",
    comm_chunks: int = compress.DEFAULT_COMM_CHUNKS,
    augment_impl: str = "xla",
    sentry=None,
) -> Callable[..., tuple[TrainState, Metrics]]:
    """Epoch-compiled training: one XLA program per EPOCH, zero host work
    per step.

    TPU-first design the reference cannot express: CIFAR fits in HBM (~150 MB
    uint8), so the whole dataset lives ON DEVICE and each step's shuffled
    global batch is gathered by index inside a ``lax.scan`` over the epoch —
    no per-step ``device_put``, no dispatch latency, no host jitter. The
    host's only per-epoch work is drawing the shuffle permutation (a
    (steps, global_batch) int32 array) and reading the loss history back.

    ``residency`` picks the on-device storage layout: ``"replicated"`` keeps
    the full dataset in every chip's HBM (upload via ``mesh.put_replicated``);
    ``"sharded"`` keeps only ``N/n_data`` contiguous rows per data-axis shard
    (upload via ``mesh.put_row_sharded``) and reassembles each step's batch
    with one O(global_batch)-byte ``psum`` inside the scan — see
    :func:`_sharded_rows_global_batch` and docs/PERF.md "Dataset residency".
    Both layouts index the same rows in the same order, so their loss
    histories agree to the usual cross-program tolerances (test-asserted).

    Returned callable: ``(state, images_all, idx_epoch, base_key, step0) ->
    (state, {"loss": (steps,)})`` where ``images_all`` is the full uint8
    dataset (placed per ``residency``), ``idx_epoch`` is ``(steps,
    global_batch)`` int32 row indices, ``base_key`` the run's PRNG key, and
    ``step0`` the global step index of the epoch's first step. Per-step keys
    are derived as ``fold_in(base_key, step0 + i)`` — identical to the
    per-step loop in ``main.py``, so an epoch-compiled run consumes the same
    data order and RNG streams and is numerically equivalent to the
    dispatch-per-step run (test-asserted; exact bitwise equality is NOT
    guaranteed because XLA fuses the scan body differently from the
    standalone step, reordering bfloat16 roundings).
    """
    per_step = _make_local_pretrain_step(
        model, tx,
        temperature=temperature, strength=strength, negatives=negatives,
        fused=fused, forward_mode=forward_mode, remat=remat, out_size=out_size,
        grad_allreduce=grad_allreduce,
        comm_overlap=comm_overlap, comm_chunks=comm_chunks,
        augment_impl=augment_impl,
    )
    return _watch(
        _make_epoch_fn(per_step, mesh, n_arrays=1, residency=residency),
        sentry,
        "pretrain_epoch",
        steps_from_args=_epoch_steps_from_args(1),
    )


def _sharded_rows_global_batch(local_rows, idx_step):
    """Reassemble a step's full global batch from row-sharded residency.

    Inside ``shard_map``, ``local_rows`` is this shard's contiguous block of
    ``rows_per_shard = ceil(N / n_data)`` dataset rows (shard ``k`` owns
    global rows ``[k*rows_per_shard, (k+1)*rows_per_shard)`` — the
    ``mesh.put_row_sharded`` layout) and ``idx_step`` is the replicated
    (global_batch,) index vector. Each shard takes the rows it owns, masked
    to zero elsewhere, and one ``psum`` over the data axis sums the
    contributions into the exact full batch: every global index has exactly
    one owner, so the sum is a disjoint union — exact in any dtype, no uint8
    overflow. Comm volume is O(global_batch * row_bytes) per step (~1.5 MiB
    at batch 512 on CIFAR uint8), <0.1% of the step's HBM traffic.
    """
    shard = jax.lax.axis_index(DATA_AXIS)
    rows_per_shard = local_rows.shape[0]
    rel = idx_step - shard * rows_per_shard
    owned = (rel >= 0) & (rel < rows_per_shard)
    picked = jnp.take(local_rows, jnp.where(owned, rel, 0), axis=0)
    mask = owned.reshape(owned.shape + (1,) * (local_rows.ndim - 1))
    contrib = jnp.where(mask, picked, jnp.zeros((), local_rows.dtype))
    return jax.lax.psum(contrib, DATA_AXIS)


def _make_epoch_fn(per_step, mesh, *, n_arrays: int, residency: str = "replicated"):
    """Wrap a per-replica step into the epoch ``lax.scan`` scaffolding.

    Shared by the pretrain (images) and supervised (images, labels) epoch
    paths so the SPMD mechanics — per-shard index slicing, on-device gather
    of each per-sample array, per-step key folding — exist once.

    ``residency="replicated"``: each per-sample array enters replicated and
    every shard gathers its local batch rows directly. ``"sharded"``: each
    array enters row-sharded over the data axis (``in_specs=P(DATA_AXIS)``)
    and the step batch is first reassembled by
    :func:`_sharded_rows_global_batch` before the local slice is taken —
    same rows, same order, ``n_data``× less HBM per chip.

    Returned callable: ``(state, *arrays, idx_epoch, base_key, step0) ->
    (state, metrics_history)`` with each metrics leaf stacked to (steps,).
    """
    if residency not in RESIDENCIES:
        raise ValueError(
            f"residency must be one of {RESIDENCIES}, got {residency!r}"
        )

    def local_epoch(state: TrainState, *rest):
        arrays = rest[:n_arrays]
        idx_epoch, base_key, step0 = rest[n_arrays:]
        shard = jax.lax.axis_index(DATA_AXIS)
        n_local = idx_epoch.shape[1] // jax.lax.axis_size(DATA_AXIS)

        def body(state, xs):
            idx_step, i = xs
            local_idx = jax.lax.dynamic_slice_in_dim(
                idx_step, shard * n_local, n_local
            )
            if residency == "replicated":
                gathered = [jnp.take(a, local_idx, axis=0) for a in arrays]
            else:
                gathered = [
                    jax.lax.dynamic_slice_in_dim(
                        _sharded_rows_global_batch(a, idx_step),
                        shard * n_local,
                        n_local,
                    )
                    for a in arrays
                ]
            return per_step(
                state, *gathered, jax.random.fold_in(base_key, step0 + i)
            )

        steps = idx_epoch.shape[0]
        return jax.lax.scan(
            body, state, (idx_epoch, jnp.arange(steps, dtype=jnp.int32))
        )

    array_spec = _REP if residency == "replicated" else _BATCH
    sharded = jax.shard_map(
        local_epoch,
        mesh=mesh,
        in_specs=(_REP,) + (array_spec,) * n_arrays + (_REP,) * 3,
        out_specs=_REP,
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0,))


def _local_resident_block(a, residency: str):
    """This shard's contiguous row block of a device-resident split.

    Inside ``shard_map``: under ``sharded`` residency the local array IS the
    block (``mesh.put_row_sharded`` layout); under ``replicated`` residency
    every shard holds the full split and slices its ``[k*R, (k+1)*R)`` rows,
    which requires the row count to divide by the data-axis size (callers
    tail-pad before upload — the shapes are static, so a bad pad fails at
    trace time, not silently)."""
    if residency == "sharded":
        return a
    n_shards = jax.lax.axis_size(DATA_AXIS)
    if a.shape[0] % n_shards:
        raise ValueError(
            f"replicated split of {a.shape[0]} rows does not divide over "
            f"{n_shards} data shards; tail-pad the upload to a multiple"
        )
    rows = a.shape[0] // n_shards
    return jax.lax.dynamic_slice_in_dim(
        a, jax.lax.axis_index(DATA_AXIS) * rows, rows
    )


def _make_superepoch_fn(
    per_step, mesh, *, n_arrays: int, residency: str = "replicated",
    monitor=None,
):
    """Wrap a per-replica step into a SUPEREPOCH ``lax.scan`` — an outer
    scan over K epochs nested around the per-epoch step scan, all inside
    ONE ``shard_map``/jit, so one compiled XLA program runs K full epochs
    (and, optionally, the in-program centroid monitor at epoch boundaries)
    with zero host syncs in between.

    Contract without ``monitor``::

        (state, *arrays, idx_super, base_key, step0)
            -> (state, {metric: (K, steps)})

    with ``idx_super`` the ``(K, steps, global_batch)`` int32 stack of K
    epoch index matrices. Per-step RNG keys fold on the ABSOLUTE step index
    ``step0 + k*steps + i`` — the same stream as K sequential
    :func:`_make_epoch_fn` calls, so a K-superepoch is numerically
    equivalent to K single-epoch calls (test-asserted, usual cross-program
    tolerances).

    With ``monitor`` (a per-shard probe from
    ``eval.make_local_centroid_monitor``) the contract widens to::

        (state, *arrays, train_labels, test_rows, test_labels,
         idx_super, probe_mask, base_key, step0)
            -> (state, {metric: (K, steps), "monitor/<name>": (K,)})

    where ``probe_mask`` is a (K,) bool — the host-evaluated
    ``eval_every`` predicate per epoch in the chunk — and probe rows for
    unprobed epochs are NaN-filled (the ``lax.cond`` skip branch).
    ``test_rows`` is placed per the same ``residency`` as the train arrays;
    labels enter replicated, padded to ``n_shards * rows_per_shard``.
    """
    if residency not in RESIDENCIES:
        raise ValueError(
            f"residency must be one of {RESIDENCIES}, got {residency!r}"
        )

    def local_super(state: TrainState, *rest):
        arrays = rest[:n_arrays]
        if monitor is not None:
            train_labels, test_rows, test_labels = rest[n_arrays:n_arrays + 3]
            idx_super, probe_mask, base_key, step0 = rest[n_arrays + 3:]
        else:
            idx_super, base_key, step0 = rest[n_arrays:]
        shard = jax.lax.axis_index(DATA_AXIS)
        steps = idx_super.shape[1]
        n_local = idx_super.shape[2] // jax.lax.axis_size(DATA_AXIS)

        def step_body(state, xs):
            idx_step, i = xs
            local_idx = jax.lax.dynamic_slice_in_dim(
                idx_step, shard * n_local, n_local
            )
            if residency == "replicated":
                gathered = [jnp.take(a, local_idx, axis=0) for a in arrays]
            else:
                gathered = [
                    jax.lax.dynamic_slice_in_dim(
                        _sharded_rows_global_batch(a, idx_step),
                        shard * n_local,
                        n_local,
                    )
                    for a in arrays
                ]
            return per_step(
                state, *gathered, jax.random.fold_in(base_key, step0 + i)
            )

        def epoch_body(state, xs):
            if monitor is not None:
                idx_epoch, k, pm = xs
            else:
                idx_epoch, k = xs
            offsets = k * steps + jnp.arange(steps, dtype=jnp.int32)
            state, hist = jax.lax.scan(step_body, state, (idx_epoch, offsets))
            if monitor is not None:
                def run(s):
                    return monitor(
                        s.params, s.batch_stats,
                        _local_resident_block(arrays[0], residency),
                        train_labels,
                        _local_resident_block(test_rows, residency),
                        test_labels,
                    )

                def skip(s):
                    return {
                        name: jnp.full((), jnp.nan, jnp.float32)
                        for name in monitor.metric_names
                    }

                probe = jax.lax.cond(pm, run, skip, state)
                hist = dict(hist) | {
                    f"monitor/{name}": v for name, v in probe.items()
                }
            return state, hist

        n_epochs = idx_super.shape[0]
        epoch_ids = jnp.arange(n_epochs, dtype=jnp.int32)
        xs = (
            (idx_super, epoch_ids, probe_mask)
            if monitor is not None
            else (idx_super, epoch_ids)
        )
        return jax.lax.scan(epoch_body, state, xs)

    array_spec = _REP if residency == "replicated" else _BATCH
    probe_specs = (_REP, array_spec, _REP) if monitor is not None else ()
    n_tail = 4 if monitor is not None else 3  # idx, [mask,] key, step0
    sharded = jax.shard_map(
        local_super,
        mesh=mesh,
        in_specs=(_REP,) + (array_spec,) * n_arrays + probe_specs
        + (_REP,) * n_tail,
        out_specs=_REP,
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0,))


def make_pretrain_superepoch_fn(
    model,
    tx: optax.GradientTransformation,
    mesh,
    *,
    temperature: float = 0.5,
    strength: float = 0.5,
    negatives: str = "global",
    fused: bool = False,
    forward_mode: str = "two_pass",
    remat: bool = False,
    out_size: int = 32,
    residency: str = "replicated",
    grad_allreduce: str = "exact",
    comm_overlap: str = "off",
    comm_chunks: int = compress.DEFAULT_COMM_CHUNKS,
    augment_impl: str = "xla",
    monitor=None,
    sentry=None,
) -> Callable[..., tuple[TrainState, Metrics]]:
    """Superepoch-compiled training: ONE XLA program per K EPOCHS
    (``runtime.epochs_per_compile``), the Podracer/Anakin pattern — the host
    touches the device only at superepoch boundaries.

    The epoch body is the exact :func:`make_pretrain_epoch_fn` scan wrapped
    in an outer ``lax.scan`` over the K stacked epoch index matrices;
    metrics come back STACKED per epoch (``{"loss": (K, steps)}``) so one
    boundary fetch feeds K epochs of host bookkeeping. With ``monitor``
    (``eval.make_local_centroid_monitor``) the ``eval_every`` centroid
    probe runs inside the same program, gated per epoch by ``probe_mask``
    — monitoring costs zero host syncs. See :func:`_make_superepoch_fn`
    for the full calling convention and the RNG-equivalence guarantee.
    """
    per_step = _make_local_pretrain_step(
        model, tx,
        temperature=temperature, strength=strength, negatives=negatives,
        fused=fused, forward_mode=forward_mode, remat=remat, out_size=out_size,
        grad_allreduce=grad_allreduce,
        comm_overlap=comm_overlap, comm_chunks=comm_chunks,
        augment_impl=augment_impl,
    )
    idx_pos = 1 + 1 + (3 if monitor is not None else 0)
    return _watch(
        _make_superepoch_fn(
            per_step, mesh, n_arrays=1, residency=residency, monitor=monitor
        ),
        sentry,
        "pretrain_superepoch",
        steps_from_args=superepoch_steps_from_args(idx_pos),
    )


def _make_local_supervised_step(
    model, tx, *, strength: float, out_size: int, grad_allreduce: str = "exact",
    comm_overlap: str = "off", comm_chunks: int = compress.DEFAULT_COMM_CHUNKS,
    augment_impl: str = "xla",
):
    """Per-replica supervised CE step, shared by the dispatch-per-step and
    epoch-compiled paths (see :func:`_make_local_pretrain_step`)."""
    compress.validate_mode(grad_allreduce)
    compress.validate_overlap(comm_overlap, comm_chunks)
    validate_augment_impl(augment_impl)

    def local_step(state: TrainState, images, labels, rng):
        # same global-position key scheme as the pretrain step: the single
        # view's draw survives an elastic remesh unchanged
        keys = _global_sample_keys(rng, images.shape[0], views=1)
        rng = jax.random.fold_in(rng, jax.lax.axis_index(DATA_AXIS))
        if augment_impl == "fused":
            x = fused_one_view(rng, images, strength, out_size, keys=keys)
        else:
            aug = jax.vmap(simclr_augment_single, in_axes=(0, 0, None, None))
            x = aug(keys, to_float(images), strength, out_size)

        def loss_fn(params):
            logits, mut = model.apply(
                {"params": params, "batch_stats": state.batch_stats}, x, train=True,
                mutable=["batch_stats"],
            )
            per_example = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), labels
            )
            loss = jax.lax.pmean(per_example.mean(), DATA_AXIS)
            correct = jnp.sum(jnp.argmax(logits, -1) == labels)
            return loss, (mut["batch_stats"], correct, per_example.shape[0])

        if comm_overlap == "async":
            # staged backward, same shape as the pretrain step's async path
            loss, vjp_fn, (new_stats, correct, n_local) = jax.vjp(
                loss_fn, state.params, has_aux=True
            )
            grads, = vjp_fn(jnp.ones_like(loss))
        else:
            (loss, (new_stats, correct, n_local)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.params)
        grads = compress.grad_allreduce(
            grads, DATA_AXIS, grad_allreduce,
            key=jax.random.fold_in(rng, compress.KEY_FOLD_QUANT),
            overlap=comm_overlap, chunks=comm_chunks,
        )
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1, params=params, batch_stats=new_stats, opt_state=new_opt
        )
        acc = jax.lax.psum(correct, DATA_AXIS) / jax.lax.psum(
            jnp.asarray(n_local, jnp.float32), DATA_AXIS
        )
        return new_state, {"loss": loss, "accuracy": acc}

    return local_step


def make_supervised_step(
    model,
    tx: optax.GradientTransformation,
    mesh,
    *,
    strength: float = 0.5,
    out_size: int = 32,
    grad_allreduce: str = "exact",
    comm_overlap: str = "off",
    comm_chunks: int = compress.DEFAULT_COMM_CHUNKS,
    augment_impl: str = "xla",
    sentry=None,
) -> Callable[..., tuple[TrainState, Metrics]]:
    """Jitted supervised CE train step (one SimCLR-augmented view).

    The reference's supervised baseline trains on the single-view SimCLR
    augmentation (``/root/reference/supervised.py:190,200`` uses
    ``create_simclr_data_augmentation``) with CE loss (``supervised.py:104``).
    """
    local_step = _make_local_supervised_step(
        model, tx, strength=strength, out_size=out_size,
        grad_allreduce=grad_allreduce,
        comm_overlap=comm_overlap, comm_chunks=comm_chunks,
        augment_impl=augment_impl,
    )
    sharded = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(_REP, _BATCH, _BATCH, _REP),
        out_specs=_REP,
        check_vma=False,
    )
    return _watch(
        jax.jit(sharded, donate_argnums=(0,)), sentry, "supervised_step"
    )


def make_supervised_epoch_fn(
    model,
    tx: optax.GradientTransformation,
    mesh,
    *,
    strength: float = 0.5,
    out_size: int = 32,
    residency: str = "replicated",
    grad_allreduce: str = "exact",
    comm_overlap: str = "off",
    comm_chunks: int = compress.DEFAULT_COMM_CHUNKS,
    augment_impl: str = "xla",
    sentry=None,
) -> Callable[..., tuple[TrainState, Metrics]]:
    """Epoch-compiled supervised training (see
    :func:`make_pretrain_epoch_fn` — same design: dataset resident on
    device, per-epoch ``lax.scan``, identical RNG streams to the per-step
    loop; ``residency`` shards both images and labels over the data axis).

    Returned callable: ``(state, images_all, labels_all, idx_epoch,
    base_key, step0) -> (state, {"loss": (steps,), "accuracy": (steps,)})``.
    """
    per_step = _make_local_supervised_step(
        model, tx, strength=strength, out_size=out_size,
        grad_allreduce=grad_allreduce,
        comm_overlap=comm_overlap, comm_chunks=comm_chunks,
        augment_impl=augment_impl,
    )
    return _watch(
        _make_epoch_fn(per_step, mesh, n_arrays=2, residency=residency),
        sentry,
        "supervised_epoch",
        steps_from_args=_epoch_steps_from_args(2),
    )


def make_supervised_eval_step(model, mesh) -> Callable[..., Metrics]:
    """Jitted distributed validation: global sum-loss and correct counts.

    The SPMD analogue of the reference's ``dist.barrier`` + two
    ``dist.reduce(dst=0)`` calls (``/root/reference/supervised.py:137-139``)
    — here a ``psum`` that leaves identical totals on every replica.

    Takes a per-row ``valid`` float mask so a non-divisible validation set
    can be tail-padded to the static batch shape and still evaluated in this
    one compiled path (the reference's ``drop_last=False`` semantics,
    ``supervised.py:219-223``): padded rows contribute zero loss/correct/
    count. Callers pass ``valid=1`` on real rows, ``0`` on padding.
    """

    def local_step(params, batch_stats, images, labels, valid):
        x = to_float(images)
        logits = model.apply(
            {"params": params, "batch_stats": batch_stats}, x, train=False
        ).astype(jnp.float32)
        per_example = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        sum_loss = jax.lax.psum((per_example * valid).sum(), DATA_AXIS)
        correct = jax.lax.psum(
            jnp.sum((jnp.argmax(logits, -1) == labels) * valid), DATA_AXIS
        )
        count = jax.lax.psum(valid.sum(), DATA_AXIS)
        return {"sum_loss": sum_loss, "correct": correct, "count": count}

    sharded = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(_REP, _REP, _BATCH, _BATCH, _BATCH),
        out_specs=_REP,
        check_vma=False,
    )
    return jax.jit(sharded)


@functools.lru_cache(maxsize=32)
def make_encode_step(
    model, mesh, *, use_full_encoder: bool = False
) -> Callable[..., jax.Array]:
    """Jitted frozen-feature extraction, batch-sharded in and out.

    Memoized on (model, mesh, flags) — linen Modules hash by value — so
    callers that re-enter per checkpoint or per monitoring epoch (eval.py,
    main.py's eval_every probe) reuse one traced program instead of
    re-tracing a fresh jit closure every call.

    ``use_full_encoder=False`` returns encoder features h (``model.encode``,
    reference ``eval.py:47-50`` / ``model.py:116-123``); True returns
    projection-head output z.

    Explicit in/out shardings over ``mesh`` make this a true global SPMD
    program: the batch stays sharded over the data axis end to end (the
    multi-host input side is ``mesh.put_global_batch``, the output side
    ``utils.fetch.fetch``'s process_allgather), variables are replicated.
    """
    rep = NamedSharding(mesh, _REP)
    batched = NamedSharding(mesh, _BATCH)

    @partial(
        jax.jit,
        in_shardings=(rep, rep, batched),
        out_shardings=batched,
    )
    def encode(params, batch_stats, images):
        x = to_float(images)
        variables = {"params": params, "batch_stats": batch_stats}
        if use_full_encoder:
            return model.apply(variables, x, train=False).astype(jnp.float32)
        return model.apply(
            variables, x, train=False, method=model.encode
        ).astype(jnp.float32)

    return encode


@functools.lru_cache(maxsize=32)
def make_augmented_encode_step(
    model, mesh, *, strength: float = 0.5, out_size: int = 32,
    use_full_encoder: bool = False,
) -> Callable[..., jax.Array]:
    """Features of ONE stochastic SimCLR view (feature-export averaging).

    Reference: ``convert_vectors_for_contrastive`` feeds view0 of the 2-view
    transform through the frozen model (``save_features.py:50-77,166-179``).
    Sharded over ``mesh`` like :func:`make_encode_step`.
    """
    rep = NamedSharding(mesh, _REP)
    batched = NamedSharding(mesh, _BATCH)

    @partial(
        jax.jit,
        in_shardings=(rep, rep, batched, rep),
        out_shardings=batched,
    )
    def encode(params, batch_stats, images, rng):
        keys = jax.random.split(rng, images.shape[0])
        aug = jax.vmap(simclr_augment_single, in_axes=(0, 0, None, None))
        x = aug(keys, to_float(images), strength, out_size)
        variables = {"params": params, "batch_stats": batch_stats}
        if use_full_encoder:
            return model.apply(variables, x, train=False).astype(jnp.float32)
        return model.apply(
            variables, x, train=False, method=model.encode
        ).astype(jnp.float32)

    return encode
