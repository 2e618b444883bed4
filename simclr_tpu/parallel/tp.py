"""Tensor parallelism over the ``model`` mesh axis (projection head).

Round 1 reserved the 2-D ``(data, model)`` mesh with ``model`` unused
(SURVEY §2.3: "design mesh so a `model` axis can be added later"). This
module makes that axis real: the SimCLR projection head runs Megatron-style
tensor-parallel — ``linear1`` column-parallel, ``bn1``/relu on local
channels, ``linear2`` row-parallel with a ``psum`` over the model axis
completing the contraction (``models/heads.py:ProjectionHead``).

Design (shard_map + GSPMD hybrid):

  * **Global view for state.** Params/optimizer/checkpoints always hold the
    full (global) arrays, laid out with :func:`tp_state_shardings` — the head
    leaves sharded over ``model`` (``linear1.kernel P(None,'model')``,
    ``bn1.* P('model')``, ``linear2.kernel P('model',None)``), everything
    else replicated. Checkpoint/resume and the torch-import shim are
    untouched.
  * **Local view for compute.** Inside ``shard_map`` each shard sees its
    slice; the forward runs a local-view model (``head_hidden = hidden//tp``,
    ``head_tp_axis=MODEL_AXIS``) so Flax's parameter shape checks match the
    slices.
  * **Backward collectives via f/g boundary operators.** Under
    ``check_vma=False`` a raw forward ``psum`` transposes to ``psum``, which
    scales replicated cotangents by the axis size. The head therefore wraps
    its TP region in Megatron's f/g pair (``models/heads.py``): identity-
    forward/psum-backward at the input (completing the partial ``dL/dh``),
    psum-forward/identity-backward at the output. Gradients then leave the
    shard_map already correct — no per-leaf fixups here.
  * **Optimizer at the jit level.** ``tx.update`` runs OUTSIDE shard_map on
    the globally-sharded pytrees, so LARS trust-ratio norms are GLOBAL param
    and grad norms — XLA inserts the cross-shard reductions. Running the
    update inside shard_map would silently compute per-shard norms for the
    head and diverge from the unsharded recipe.

Equivalence is tested in tests/test_tp.py: a (d, m) mesh step matches the
(d, 1) degenerate step loss- and param-wise, and the sharded head forward
matches the unsharded module output.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from simclr_tpu.models.arch import FEATURE_DIMS
from simclr_tpu.obs.trace import (
    SCOPE_AUGMENT,
    SCOPE_ENCODER,
    SCOPE_GRAD_REDUCE,
    SCOPE_LARS,
    SCOPE_NTXENT,
)
from simclr_tpu.ops.augment_pallas import validate_impl as validate_augment_impl
from simclr_tpu.parallel import compress
from simclr_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from simclr_tpu.parallel.steps import (
    _augment_two_views,
    _forward_fn,
    _global_sample_keys,
    _ntxent,
    _watch,
)
from simclr_tpu.parallel.train_state import TrainState


def _names(path) -> list[str]:
    """Trailing DictKey names of a pytree path (works for params,
    batch_stats, and optimizer-state leaves alike — optax trace state mirrors
    the params tree under extra non-dict keys)."""
    return [str(k.key) for k in path if isinstance(k, jax.tree_util.DictKey)]


def _head_pspec(names: list[str]) -> P:
    """PartitionSpec for one leaf, by its dict-path suffix."""
    if "g" in names:
        sub = names[names.index("g"):]
        if len(sub) >= 2:
            if sub[1] == "linear1" and sub[-1] == "kernel":
                return P(None, MODEL_AXIS)  # column-parallel: out dim sharded
            if sub[1] == "linear1" and sub[-1] == "bias":
                return P(MODEL_AXIS)
            if sub[1] == "bn1":  # scale/bias params and mean/var stats
                return P(MODEL_AXIS)
            if sub[1] == "linear2" and sub[-1] == "kernel":
                return P(MODEL_AXIS, None)  # row-parallel: in dim sharded
    return P()


def tree_pspecs(tree):
    """Pytree of PartitionSpecs: head leaves sharded over ``model``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [_head_pspec(_names(path)) for path, _ in flat]
    )


def state_pspecs(state: TrainState) -> TrainState:
    """TrainState-shaped pytree of PartitionSpecs (step replicated)."""
    return TrainState(
        step=P(),
        params=tree_pspecs(state.params),
        batch_stats=tree_pspecs(state.batch_stats),
        opt_state=tree_pspecs(state.opt_state),
    )


def tp_state_shardings(mesh, state: TrainState) -> TrainState:
    """NamedSharding tree for ``jax.device_put`` of a global-view state."""
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        state_pspecs(state),
        is_leaf=lambda x: isinstance(x, P),
    )


def _local_view(model, tp: int):
    """The per-shard model the shard_map body applies (clone keeps every
    other field in lockstep with the global-view model)."""
    hidden = FEATURE_DIMS[model.base_cnn]  # the encoder's output width
    if hidden % tp:
        raise ValueError(
            f"projection hidden width {hidden} not divisible by model axis {tp}"
        )
    return model.clone(head_hidden=hidden // tp, head_tp_axis=MODEL_AXIS)


def make_pretrain_step_tp(
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
) -> Callable[[TrainState, jax.Array, jax.Array], tuple[TrainState, dict]]:
    """Contrastive train step with the projection head tensor-parallel over
    the ``model`` mesh axis: shard_map'ed forward/backward + jit-level
    optimizer update.

    Same contract and keywords as
    :func:`simclr_tpu.parallel.steps.make_pretrain_step`:
    ``(state, images_u8, rng) -> (state, metrics)``, watched by ``sentry`` as
    ``pretrain_step``; ``state`` must be laid out with
    :func:`tp_state_shardings`. With ``model=1`` this degenerates to the
    data-parallel step (tested equivalent). ``forward_mode`` other than
    ``"two_pass"`` is refused: concat's joint-BN deviation is orthogonal to
    TP and stays out of this step's equivalence surface.

    ``grad_allreduce`` compresses the DATA-axis gradient all-reduce only
    (``parallel/compress.py``); the head's model-axis f/g collectives stay
    exact. The quantization key is forked from the data-index-folded rng, so
    model-axis replicas draw identical rounding noise and replicated
    (encoder) gradients stay identical across the model axis.
    ``comm_overlap``/``comm_chunks`` likewise apply to the data-axis ring
    only — each ppermute ring runs within a model-axis replica's data ring,
    and the gather phase forwards bytes verbatim, so model-axis replicas
    still dequantize identical gradients.

    ``negatives``/``fused`` select the NT-Xent variant with the dp step's
    dispatch (``steps._ntxent``) — the loss operates on the per-data-shard
    embeddings the TP head psum-completes, so every data-axis variant
    composes with head sharding unchanged. ``crop_lengths`` (the dp step's
    keyword for rows of token documents) is refused: a sequence encoder's
    expert layer has no exchange over the model axis."""
    tp = mesh.shape[MODEL_AXIS]
    if forward_mode != "two_pass":
        raise ValueError(
            f"mesh.model={tp} (tensor parallelism) does not combine with "
            f"model.forward_mode={forward_mode!r}: only two_pass "
            "(see docs/PERF.md, tensor-parallel support matrix)"
        )
    if crop_lengths:
        raise ValueError(
            f"mesh.model={tp} with a sequence encoder ({model.base_cnn!r}, rows "
            "of token documents): its expert layer holds its share on one chip "
            "and has no exchange over a model axis; run it with mesh.model=1"
        )
    compress.validate_mode(grad_allreduce)
    compress.validate_overlap(comm_overlap, comm_chunks)
    validate_augment_impl(augment_impl)
    if negatives not in ("global", "local", "ring"):
        raise ValueError(f"negatives must be global|local|ring, got {negatives!r}")
    if fused and negatives == "ring":
        raise ValueError(
            "loss.fused does not combine with negatives='ring' (the ring loss "
            "is already blockwise); use negatives='global' with fused"
        )
    local_model = _local_view(model, tp)
    fwd = _forward_fn(local_model, remat)  # the dp step's forward/remat recipe

    def local_fwd_bwd(params, batch_stats, images, rng):
        # the dp step's exact augmentation recipe (steps.py): keys are
        # global-batch-position-indexed, so model-axis replicas agree and
        # the draw survives an elastic remesh; the quant stream below stays
        # per-data-shard via the shard-folded rng
        with jax.named_scope(SCOPE_AUGMENT):
            keys = _global_sample_keys(rng, images.shape[0], views=2)
            rng = jax.random.fold_in(rng, jax.lax.axis_index(DATA_AXIS))
            v0, v1 = _augment_two_views(
                rng, images, strength, out_size, augment_impl, keys=keys
            )

        def loss_fn(p):
            with jax.named_scope(SCOPE_ENCODER):
                z0, mut = fwd(p, batch_stats, v0)
                z1, mut = fwd(p, mut["batch_stats"], v1)
            with jax.named_scope(SCOPE_NTXENT):
                loss = _ntxent(z0, z1, negatives, fused, temperature)
            return loss, mut["batch_stats"]

        if comm_overlap == "async":
            # staged backward (see steps.make_pretrain_step): explicit
            # VJP + per-bucket ring assembly in grad_allreduce lets tail
            # buckets' data-axis rings issue under earlier backward matmuls
            loss, vjp_fn, new_stats = jax.vjp(loss_fn, params, has_aux=True)
            grads, = vjp_fn(jnp.ones_like(loss))
        else:
            (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        # same convention as steps.py: sum over the data axis (compressed
        # per grad_allreduce), BEFORE the jit-level LARS update below
        with jax.named_scope(SCOPE_GRAD_REDUCE):
            grads = compress.grad_allreduce(
                grads, DATA_AXIS, grad_allreduce,
                key=jax.random.fold_in(rng, compress.KEY_FOLD_QUANT),
                overlap=comm_overlap, chunks=comm_chunks,
            )
        # No model-axis correction here: the head's f/g boundary operators
        # (models/heads.py) own the model-axis collectives in both forward
        # and backward, so encoder grads arrive complete and replica-
        # identical and head-shard grads are exact local values — pinned by
        # tests/test_tp.py::test_tp_step_matches_degenerate_model_axis.
        return loss, grads, new_stats

    def step(state: TrainState, images: jax.Array, rng: jax.Array):
        p_specs = tree_pspecs(state.params)
        s_specs = tree_pspecs(state.batch_stats)
        sharded = jax.shard_map(
            local_fwd_bwd,
            mesh=mesh,
            in_specs=(p_specs, s_specs, P(DATA_AXIS), P()),
            out_specs=(P(), p_specs, s_specs),
            check_vma=False,
        )
        loss, grads, new_stats = sharded(state.params, state.batch_stats, images, rng)
        # jit-level (GSPMD) optimizer update: norms over the GLOBAL arrays
        with jax.named_scope(SCOPE_LARS):
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1,
            params=params,
            batch_stats=new_stats,
            opt_state=new_opt,
        )
        return new_state, {"loss": loss}

    return _watch(jax.jit(step, donate_argnums=(0,)), sentry, "pretrain_step")
