"""Compiled SPMD train/eval steps (shard_map + jit).

One jitted program per entry-point hot loop, replacing the reference's
eager-loop-plus-DDP structure (``/root/reference/main.py:104-122``,
``supervised.py:109-139``): :func:`make_pretrain_step` for ``main.py``'s
data-parallel mesh (``parallel/tp.py::make_pretrain_step_tp`` is its twin for
a model axis > 1, built from the helpers here), :func:`make_supervised_step`
and :func:`make_supervised_eval_step` for ``supervised.py``, and the two
frozen-feature encode steps. The host dispatches one step per batch; there is
no other way to run them. Each train step consumes and returns the full
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
from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from simclr_tpu.data.augment import simclr_augment_single, to_float
from simclr_tpu.data.documents import span_crop_views
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
from simclr_tpu.parallel.mesh import DATA_AXIS
from simclr_tpu.parallel.train_state import TrainState

Metrics = dict[str, jnp.ndarray]

_REP = P()          # replicated
_BATCH = P(DATA_AXIS)  # batch dim sharded over the data axis


def _watch(jit_fn, sentry, name: str):
    """Route a jitted step through the compile sentry's explicit AOT
    lower/compile path (``obs/compile.py``) so every compilation — and any
    post-warmup recompilation — is timed, fingerprinted, and cost-analyzed.
    The bare jit dispatch is returned unchanged when observability is off.
    """
    if sentry is None:
        return jit_fn
    return sentry.watch(jit_fn, name)


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
    rng, images, strength, out_size, augment_impl="xla", keys=None,
    crop_lengths=(),
):
    """Two on-device SimCLR views of the local shard, by what the data is:
    image augmentation for uint8 images, and for rows of token ids
    (``crop_lengths`` given; ``data/documents.py``) one span crop per
    document and view, packed into rows.

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
    if crop_lengths:
        return span_crop_views(keys, images, crop_lengths)
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
        # ``counters``: what a sequence encoder's expert layers sow
        # (models/sequence.py); a ResNet sows nothing and the key is absent
        return model.apply(
            {"params": params, "batch_stats": batch_stats}, v, train=True,
            mutable=["batch_stats", "counters"],
        )

    return jax.checkpoint(fwd) if remat else fwd


def _apply_two_pass(fwd, params, batch_stats, v0, v1):
    """Two sequential forwards threading BN running stats.

    Matches the reference's per-view forwards (``main.py:112-113``): each
    view's batch forms its own BN batch statistics and the running stats get
    two momentum updates per step — NOT one concatenated 2B forward.
    """
    z0, mut0 = fwd(params, batch_stats, v0)
    z1, mut1 = fwd(params, mut0["batch_stats"], v1)
    counters = jax.tree.map(
        jnp.add, mut0.get("counters", {}), mut1.get("counters", {})
    )
    return z0, z1, mut1["batch_stats"], counters


def _apply_concat(fwd, params, batch_stats, v0, v1):
    """One forward over the concatenated 2B batch (performance option).

    Halves kernel-launch/weight-streaming overhead by doubling every matmul's
    batch, at the cost of BN statistics spanning both views jointly (the
    google-research SimCLR formulation) instead of per-view — a documented
    semantic deviation behind ``model.forward_mode=concat``.
    """
    both = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0), v0, v1)
    z, mut = fwd(params, batch_stats, both)
    n = z.shape[0] // 2
    return z[:n], z[n:], mut["batch_stats"], mut.get("counters", {})


def _counter_metrics(counters) -> Metrics:
    """What the model sowed into ``counters`` in one step, summed over the
    whole batch, each leaf under its own name, as the host reads them with
    the loss (a sequence encoder's ``expert_load``, ``models/sequence.py``).
    Empty for an encoder that counts nothing."""
    return {
        path[-1].key: jax.lax.psum(leaf, DATA_AXIS)
        for path, leaf in jax.tree_util.tree_leaves_with_path(counters)
    }


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
    crop_lengths: tuple[int, ...] = (),
    sentry=None,
) -> Callable[[TrainState, jax.Array, jax.Array], tuple[TrainState, Metrics]]:
    """Build the jitted contrastive train step.

    Returned callable: ``(state, images_u8, rng) -> (state, metrics)`` with
    ``images`` the raw uint8 global batch sharded over the data axis. The
    model must be constructed with ``bn_cross_replica_axis=DATA_AXIS``.

    ``crop_lengths`` says that the rows are token documents and how a view
    packs their span crops (``data/documents.span_crop_views``); the model's
    encoder is then a sequence encoder, and ``metrics`` carries its expert
    layers' counters beside the loss (:func:`_counter_metrics`).

    ``fused=True`` routes the loss through the Pallas blockwise kernels
    (``ops/ntxent_pallas.py``), which never materialize the similarity
    matrix — worthwhile at large (global) batches. Supported with ``local``
    negatives (per-shard kernel) and ``global`` negatives (local anchors
    against the all-gathered candidate set); ``ring`` IS the streaming
    formulation already and has no fused variant.

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

    ``sentry`` (``obs/compile.py``) watches the jit as ``pretrain_step``.
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
                rng, images, strength, out_size, augment_impl, keys=keys,
                crop_lengths=crop_lengths,
            )

        def loss_fn(params):
            with jax.named_scope(SCOPE_ENCODER):
                z0, z1, new_stats, counters = apply_views(
                    forward, params, state.batch_stats, v0, v1
                )
            with jax.named_scope(SCOPE_NTXENT):
                loss = _ntxent(z0, z1, negatives, fused, temperature)
            return loss, (new_stats, counters)

        if comm_overlap == "async":
            # staged backward: explicit VJP makes the cotangent pytree a
            # first-class value whose leaves the scheduler sees individually;
            # paired with grad_allreduce's per-bucket assembly (no global
            # concatenate) each ring depends only on the leaves it spans, so
            # its hops can issue while earlier layers' backward matmuls run
            loss, vjp_fn, (new_stats, counters) = jax.vjp(
                loss_fn, state.params, has_aux=True
            )
            grads, = vjp_fn(jnp.ones_like(loss))
        else:
            (loss, (new_stats, counters)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.params)
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
        metrics = {"loss": loss, **_counter_metrics(counters)}
        return new_state, metrics

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
    """Jitted supervised CE train step (one SimCLR-augmented view):
    ``(state, images_u8, labels, rng) -> (state, {"loss", "accuracy"})``.

    The reference's supervised baseline trains on the single-view SimCLR
    augmentation (``/root/reference/supervised.py:190,200`` uses
    ``create_simclr_data_augmentation``) with CE loss (``supervised.py:104``).
    The gradient-reduce keywords are :func:`make_pretrain_step`'s.
    """
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
