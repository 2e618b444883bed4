"""NT-Xent (normalized temperature-scaled cross-entropy) for TPU.

The algorithmic core of SimCLR, re-derived for XLA rather than translated:
the reference builds three masked similarity blocks with boolean-mask
compaction to N x (N-1) (``/root/reference/loss.py:42-52``) — a dynamic-shape
pattern XLA can't tile. We instead compute the full (2N)x(2N) similarity
matrix of the concatenated views, mask self-similarity additively (static
shapes, one MXU matmul), and take cross-entropy against the partner index.
For every anchor the candidate set is the same 2N-1 elements the reference
uses, so the losses are mathematically identical (verified in
tests/test_ntxent.py against an independent naive implementation).

Three entry points covering the reference + the TPU scaling axis (SURVEY §2.3):
  * :func:`ntxent_loss` — loss over whatever batch it is handed. Under a
    GSPMD ``jit`` with the batch sharded over the data axis this IS the
    global-negatives loss (XLA shards the matmul and inserts collectives).
  * :func:`ntxent_loss_sharded_rows` — explicit-collective version for use
    inside ``shard_map``: all-gathers the (small, N x d) embeddings over the
    data axis, computes only the local anchors' rows of the similarity
    matrix, and pmeans. Global negatives with O(local x global) memory.
  * :func:`ntxent_loss_local_negatives` — the reference's semantics: each
    replica sees only its own batch as negatives (negatives per sample =
    2*B_local - 2, ``/root/reference/loss.py:25-36``), kept as a config
    switch for parity experiments.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


_NEG_INF = -1e9  # additive mask; safe in float32 logsumexp


def _l2_normalize(z: jnp.ndarray, eps: float = 1e-12) -> jnp.ndarray:
    z = z.astype(jnp.float32)
    return z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True), eps)


def _reduce(per_anchor: jnp.ndarray, reduction: str) -> jnp.ndarray:
    if reduction == "mean":
        return per_anchor.mean()
    if reduction == "sum":
        return per_anchor.sum()
    if reduction == "none":
        return per_anchor
    raise ValueError(f"reduction must be mean|sum|none, got {reduction!r}")


def _anchor_losses(
    anchors: jnp.ndarray,
    candidates: jnp.ndarray,
    self_idx: jnp.ndarray,
    pos_idx: jnp.ndarray,
    temperature: float,
) -> jnp.ndarray:
    """Per-anchor NT-Xent loss rows.

    anchors (M, d) and candidates (K, d) must be L2-normalized; ``self_idx``
    is each anchor's own column (masked out), ``pos_idx`` its positive's.
    """
    sim = (anchors @ candidates.T) / temperature  # (M, K) float32 on MXU
    m = anchors.shape[0]
    rows = jnp.arange(m)
    sim = sim.at[rows, self_idx].add(_NEG_INF)
    pos = sim[rows, pos_idx]
    return jax.nn.logsumexp(sim, axis=1) - pos


def ntxent_loss(
    z0: jnp.ndarray,
    z1: jnp.ndarray,
    temperature: float = 0.5,
    reduction: str = "mean",
) -> jnp.ndarray:
    """NT-Xent over the full batch given (both views, (N, d) each).

    ``reduction='mean'`` divides the summed two-view loss by 2N, matching the
    reference's mean semantics (``/root/reference/loss.py:65``). ``'none'``
    returns the (2N,) per-anchor vector, view-0 anchors first.
    """
    if z0.shape != z1.shape:
        raise ValueError(
            f"view embeddings must have identical shapes, got {z0.shape} vs {z1.shape}"
        )
    n = z0.shape[0]
    z = _l2_normalize(jnp.concatenate([z0, z1], axis=0))  # (2N, d)
    idx = jnp.arange(2 * n)
    pos_idx = (idx + n) % (2 * n)  # partner view is the positive
    per_anchor = _anchor_losses(z, z, idx, pos_idx, temperature)
    return _reduce(per_anchor, reduction)


def gather_global_candidates(
    z0: jnp.ndarray, z1: jnp.ndarray, axis_name: str
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Shared layout for gathered-negative losses, inside ``shard_map``.

    Returns ``(z_local, candidates, self_idx, pos_idx)``: normalized local
    anchors ``[z0_local | z1_local]``, the all-gathered candidate set
    ``[all z0 | all z1]``, and each local anchor's own / positive global
    column. Both the XLA (:func:`ntxent_loss_sharded_rows`) and Pallas-fused
    (``ntxent_pallas.ntxent_loss_fused_sharded``) losses consume exactly this
    layout — keep it single-sourced so their self-mask columns can never
    drift apart (their parity is test-asserted).
    """
    n_local = z0.shape[0]
    shard = jax.lax.axis_index(axis_name)
    n_shards = jax.lax.axis_size(axis_name)
    n_global = n_local * n_shards

    z_local = _l2_normalize(jnp.concatenate([z0, z1], axis=0))  # (2n_local, d)
    # gathered layout: [shard0 z0 | shard1 z0 | ... | shard0 z1 | shard1 z1 ...]
    g0 = jax.lax.all_gather(z_local[:n_local], axis_name, tiled=True)
    g1 = jax.lax.all_gather(z_local[n_local:], axis_name, tiled=True)
    candidates = jnp.concatenate([g0, g1], axis=0)  # (2*n_global, d)

    local_rows = jnp.arange(n_local)
    idx0 = shard * n_local + local_rows          # global cols of local view-0
    idx1 = n_global + idx0                       # global cols of local view-1
    self_idx = jnp.concatenate([idx0, idx1])
    pos_idx = jnp.concatenate([idx1, idx0])
    return z_local, candidates, self_idx, pos_idx


def ntxent_loss_sharded_rows(
    z0: jnp.ndarray,
    z1: jnp.ndarray,
    axis_name: str,
    temperature: float = 0.5,
) -> jnp.ndarray:
    """Global-negatives NT-Xent inside ``shard_map``/``pmap``.

    Gathers embeddings (cheap: activations, not params — SURVEY §5.7) over
    ``axis_name`` to form the global candidate set, but computes similarity
    rows only for local anchors. Returns the global mean loss (identical on
    every replica); gradients flow through the gather (its transpose is a
    psum-scatter, so each replica ends up with exactly its local grads).
    """
    z_local, candidates, self_idx, pos_idx = gather_global_candidates(
        z0, z1, axis_name
    )
    per_anchor = _anchor_losses(z_local, candidates, self_idx, pos_idx, temperature)
    # mean over ALL global anchors = pmean of local means
    return jax.lax.pmean(per_anchor.mean(), axis_name)


def ntxent_loss_local_negatives(
    z0: jnp.ndarray,
    z1: jnp.ndarray,
    axis_name: str | None = None,
    temperature: float = 0.5,
) -> jnp.ndarray:
    """Reference-parity NT-Xent: negatives restricted to the local replica.

    Inside ``shard_map`` each replica computes the loss on its own shard and
    the result is pmean'd — exactly the reference's DDP objective, where each
    GPU's ``NT_Xent`` sees only its local 2B embeddings and gradients are
    averaged by the all-reduce.
    """
    loss = ntxent_loss(z0, z1, temperature=temperature, reduction="mean")
    if axis_name is not None:
        loss = jax.lax.pmean(loss, axis_name)
    return loss
