"""Compressed gradient all-reduce over a mesh axis (``parallel.grad_allreduce``).

SimCLR's large-batch recipe is communication-bound the moment it leaves one
chip: every step all-reduces the full fp32 gradient pytree, and at multihost
scale that crosses DCN where bandwidth — not the MXU — sets the step floor
(EQuARX, PAPERS.md). This module is the drop-in replacement for the
``jax.lax.psum(grads, DATA_AXIS)`` sites in ``parallel/steps.py`` and
``parallel/tp.py``, selected by the ``parallel.grad_allreduce`` knob:

  * ``exact`` — the plain fp32 ``psum`` (default; bitwise-identical to the
    pre-knob behavior).
  * ``bf16``  — cast → ``psum`` → cast back. Halves wire bytes; the mantissa
    truncation is deterministic (biased toward zero) but tiny relative to
    LARS' trust-ratio normalization.
  * ``int8``  — bucketed stochastic-rounding quantization, ~3.98x fewer wire
    bytes than fp32 at the default bucket size (see
    :func:`allreduce_wire_bytes`). Unbiased: E[dequant(quant(x))] = x.

The int8 reduction keeps the WIRE format int8 end to end by decomposing the
all-reduce the way a ring all-reduce does — a reduce-scatter phase and an
all-gather phase — with the summation lifted out of the network:

  1. flatten the pytree to one fp32 vector, pad, and cut into fixed-size
     buckets; quantize each bucket as ``q = floor(x / scale + u)`` with
     ``scale = amax(|bucket|) / 127`` and ``u ~ Uniform[0, 1)`` drawn from
     the per-step PRNG key (stochastic rounding — the estimator is unbiased
     and, because the key is threaded from the train step, reproducible);
  2. *scatter*: ``all_to_all`` the int8 buckets (plus the tiny fp32 scale
     vector) so each device receives every peer's copy of the bucket range
     it owns — this is ``psum_scatter`` with the sum deferred, because int8
     partial sums would overflow and carry no shared scale;
  3. *local dequant-accumulate*: each device sums its owned range in fp32;
  4. *gather*: requantize the reduced range (fresh stochastic rounding, a
     folded key) and ``all_gather`` it back as int8; every device
     dequantizes and unflattens into the original pytree structure.

Both phases ship int8 payloads; the only fp32 on the wire is one scale per
``bucket_size`` elements (1/256 overhead at the default 1024).

Ordering contract (L2): compression replaces the gradient ``psum`` and
therefore runs BEFORE the optimizer — quantize-before-LARS, never after.
LARS (``ops/lars.py``) rescales each layer by ``||p|| / ||g||``; feeding it
the identical dequantized gradient on every replica keeps the trust ratios
replica-identical, whereas quantizing the *update* after the trust ratio
would break that and compound the error through the momentum buffer.

TP note: compression applies to the DATA axis only. Model-axis collectives
(the activation gathers/reduce-scatters inside ``models/heads.py`` and the
head-gradient psums) stay exact — they carry activations, not gradients,
and sit on fast ICI, not DCN. ``tp.py`` folds its PRNG key with the data
axis index only, so model-axis replicas draw identical rounding noise and
replicated-parameter gradients stay bitwise identical across the model axis
after dequantization.

Overlap (``parallel.comm_overlap``): the single-shot paths above emit ONE
fused collective tail after the backward — nothing for XLA's latency-hiding
scheduler to interleave. ``comm_overlap=chunked`` (+ ``parallel.comm_chunks``)
instead cuts the flattened gradient into N layer-ordered chunks and reduces
each as an explicit software ring: ``jax.lax.ppermute`` reduce-scatter hops
(each hop ships one segment per device; for int8 the running partial sum is
requantized per hop in PR 4's bucket format, since int8 partials would
overflow and carry no shared scale) followed by ``ppermute`` all-gather hops
that forward each owner's payload verbatim. Chunk i's hops are
data-independent of chunk i+1's quant/dequant compute, so the scheduler can
overlap wire time with compute instead of serializing one tail. Per-chunk
PRNG keys are folded off the same ``KEY_FOLD_QUANT``-derived key
(``fold_in(key, chunk_idx)``), and ``comm_overlap=off`` routes through the
unmodified single-shot code paths — bitwise-identical to PR 4's behavior.
The gather phase forwards each reduced segment's bytes unchanged, so every
device dequantizes identical payloads and the replica-bitwise-identical
invariant survives chunking.

``comm_overlap=async`` goes one step further: the same ring decomposition
with the same per-chunk key schedule, but each bucket is assembled from only
the gradient leaves it spans (no global concatenate) and the rings are
issued in reverse bucket order — the order reverse-mode AD materializes
cotangents — so a tail bucket's wire hops are data-independent of the head
layers' backward matmuls and can be hidden under them (paired with the
staged backward in ``parallel/steps.py`` and the async-collective XLA flags
from ``parallel/mesh.py``). Because the bucket boundaries, wire format, and
``fold_in(key, chunk_idx)`` schedule are identical to ``chunked``, ``async``
hands LARS the *same dequantized gradient* (bitwise under int8) — only the
schedule changes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


GRAD_ALLREDUCE_MODES = ("exact", "bf16", "int8")

# weight-storage formats for the serve tier (serve.weights): the same
# bucketed int8 format as the gradient wire path, but quantized ONCE at
# load time with DETERMINISTIC round-to-nearest — serving must be
# bitwise-repeatable across calls and across replicas, so the stochastic
# rounding that makes the gradient estimator unbiased is exactly wrong here
WEIGHT_QUANT_MODES = ("exact", "bf16", "int8")

# storage formats for the serve tier's retrieval corpus (serve.corpus_dtype):
# fp32 keeps the exact row-sharded matrix; int8 stores each shard's row block
# in the same deterministic bucket format as WEIGHT_QUANT_MODES' int8 (one
# fp32 scale per DEFAULT_BUCKET_SIZE elements, round-to-nearest) and
# dequantizes INSIDE the jitted scoring kernel — ~3.98x more rows per device
CORPUS_DTYPE_MODES = ("fp32", "int8")

# overlap strategy for the gradient all-reduce: "off" is the single-shot
# fused-collective path (bitwise-identical to PR 4), "chunked" decomposes it
# into parallel.comm_chunks independent ppermute rings XLA can overlap, and
# "async" additionally assembles each ring's bucket from only the leaves it
# spans — issued eagerly (last layers first) so the rings are data-ready
# while earlier layers' backward matmuls are still in flight
COMM_OVERLAP_MODES = ("off", "chunked", "async")

# default chunk count for comm_overlap=chunked: enough independent rings to
# hide wire latency under compute without shrinking messages below the
# bandwidth-efficient size at ResNet-18/50 gradient counts
DEFAULT_COMM_CHUNKS = 4

# upper bound on comm_chunks: beyond this the per-chunk segments at real
# model sizes fall under a bucket per device and padding dominates the wire
MAX_COMM_CHUNKS = 64

# elements per quantization bucket: one fp32 scale per bucket is the wire
# overhead (4/1024 -> 0.4%), while smaller buckets track the gradient's
# dynamic range more tightly. 1024 matches EQuARX's block size ballpark.
DEFAULT_BUCKET_SIZE = 1024

# fold_in tag forking the quantization PRNG stream off the train step's
# per-step rng: the augmentation stream splits the same rng, so the tag
# keeps the two streams disjoint (steps.py / tp.py use this constant)
KEY_FOLD_QUANT = 0x71

# int8 symmetric range [-127, 127]; -128 is left unused so the scale is the
# same magnitude in both directions
_QMAX = 127.0


def validate_mode(mode: str) -> str:
    """Reject unknown modes with the valid set spelled out (config + runtime)."""
    if mode not in GRAD_ALLREDUCE_MODES:
        raise ValueError(
            f"parallel.grad_allreduce must be one of {GRAD_ALLREDUCE_MODES}, "
            f"got {mode!r}"
        )
    return mode


def normalize_overlap(value) -> str:
    """Map YAML 1.1's bool reading of a bare ``off`` back to the mode name.

    ``yaml.safe_load("off")`` is False — which hits both conf files and
    ``parallel.comm_overlap=off`` CLI overrides — so the config boundary
    funnels through this before validation. Everything else passes through
    untouched for :func:`validate_overlap` to judge.
    """
    return "off" if value is False else value


def validate_overlap(overlap: str, chunks: int | None = None) -> str:
    """Reject unknown overlap modes / out-of-range chunk counts, with the
    valid set and range spelled out (config validation + runtime share this).
    """
    if overlap not in COMM_OVERLAP_MODES:
        raise ValueError(
            f"parallel.comm_overlap must be one of {COMM_OVERLAP_MODES}, "
            f"got {overlap!r}"
        )
    if chunks is not None:
        if int(chunks) != chunks or not (1 <= int(chunks) <= MAX_COMM_CHUNKS):
            raise ValueError(
                f"parallel.comm_chunks must be an int in [1, {MAX_COMM_CHUNKS}], "
                f"got {chunks!r}"
            )
    return overlap


def validate_weight_mode(mode: str) -> str:
    """Reject unknown serve.weights modes with the valid set spelled out."""
    if mode not in WEIGHT_QUANT_MODES:
        raise ValueError(
            f"serve.weights must be one of {WEIGHT_QUANT_MODES}, got {mode!r}"
        )
    return mode


def quantize_weight_buckets(
    flat: np.ndarray, bucket_size: int = DEFAULT_BUCKET_SIZE
) -> tuple[np.ndarray, np.ndarray]:
    """Deterministic bucketed int8 quantization of a flat fp32 weight vector.

    The storage counterpart of :func:`_quantize`: the same bucket format
    (``scale = amax(|bucket|) / 127``, one fp32 scale per ``bucket_size``
    elements, all-zero buckets get scale 0) but **round-to-nearest** instead
    of stochastic rounding — weights are quantized once at engine load, and
    the serve tier's bitwise-repeatability contract requires the same input
    to produce the same int8 bytes on every load and every replica. Runs on
    the host (numpy) so load-time quantization allocates nothing on device.

    Returns ``(q, scales)``: ``q`` int8 of shape ``(n_buckets, bucket_size)``
    (tail zero-padded), ``scales`` fp32 of shape ``(n_buckets,)``.
    """
    flat = np.asarray(flat, np.float32).reshape(-1)
    n_buckets = -(-flat.size // bucket_size) if flat.size else 1
    x = np.zeros((n_buckets * bucket_size,), np.float32)
    x[: flat.size] = flat
    x = x.reshape(n_buckets, bucket_size)
    scale = (np.max(np.abs(x), axis=1) / _QMAX).astype(np.float32)
    safe = np.where(scale > 0.0, scale, 1.0)
    q = np.clip(np.rint(x / safe[:, None]), -_QMAX, _QMAX)
    return q.astype(np.int8), scale


def dequantize_weight_buckets(q, scales, n_elements: int):
    """Inverse of :func:`quantize_weight_buckets`; jnp, traceable under jit.

    This is the dequantize-on-load half of the serve tier's int8 weight
    path: it runs INSIDE the jitted forward, so HBM holds only the int8
    buckets + fp32 scales and the fp32 weights exist transiently per call.
    """
    x = q.astype(jnp.float32) * scales[:, None]
    return x.reshape(-1)[:n_elements]


def weight_storage_bytes(
    n_elements: int, mode: str, *, bucket_size: int = DEFAULT_BUCKET_SIZE
) -> int:
    """Analytic resident bytes for ``n_elements`` weights under a storage mode.

    The serve-tier sibling of :func:`allreduce_wire_bytes`: exact = 4 B/elem
    (fp32), bf16 = 2 B/elem, int8 = 1 B/elem padded to whole buckets plus
    one fp32 scale per bucket (~3.98x under fp32 at the default bucket
    size). Rendered per replica next to the measured gauge so the two can
    be reconciled.
    """
    validate_weight_mode(mode)
    n = int(n_elements)
    if mode == "exact":
        return 4 * n
    if mode == "bf16":
        return 2 * n
    n_buckets = -(-n // bucket_size) if n else 1
    return n_buckets * bucket_size + 4 * n_buckets


def validate_corpus_dtype(mode: str) -> str:
    """Reject unknown serve.corpus_dtype modes with the valid set spelled out."""
    if mode not in CORPUS_DTYPE_MODES:
        raise ValueError(
            f"serve.corpus_dtype must be one of {CORPUS_DTYPE_MODES}, got {mode!r}"
        )
    return mode


def corpus_storage_bytes(
    n_rows: int,
    dim: int,
    mode: str,
    *,
    shards: int = 1,
    bucket_size: int = DEFAULT_BUCKET_SIZE,
) -> int:
    """Analytic resident HBM bytes for a row-sharded retrieval corpus.

    The corpus sibling of :func:`weight_storage_bytes`. Rows are ceil-split
    over ``shards`` devices (each shard padded to the common per-shard row
    count R = ceil(n_rows / shards)); fp32 costs 4·R·d per shard, int8 packs
    each shard's (R·d,) block into whole buckets (1 B/elem) plus one fp32
    scale per bucket — ``4 / (1 + 4/bucket_size)`` ≈ 3.98x under fp32 at the
    default bucket size. ``hbm_state()`` reports the measured twin of this
    number so the two can be reconciled in tests and the runbook.
    """
    validate_corpus_dtype(mode)
    s = max(int(shards), 1)
    rows_per_shard = -(-int(n_rows) // s) if n_rows else 0
    elems = rows_per_shard * int(dim)
    if mode == "fp32":
        return 4 * elems * s
    n_buckets = -(-elems // bucket_size) if elems else 1
    return s * (n_buckets * bucket_size + 4 * n_buckets)


def _chunk_bounds(n_elements: int, chunks: int) -> list[tuple[int, int]]:
    """Ceil-split [0, n_elements) into up to ``chunks`` contiguous pieces.

    Layer order is preserved (chunk 0 holds the first layers' gradients);
    non-divisible sizes leave the last chunk short, and chunk counts larger
    than the element count simply produce fewer (single-element) chunks —
    never an empty ring.
    """
    size = -(-n_elements // max(int(chunks), 1))
    bounds, start = [], 0
    while start < n_elements:
        stop = min(start + size, n_elements)
        bounds.append((start, stop))
        start = stop
    return bounds


def allreduce_wire_bytes(
    n_elements: int,
    n_devices: int,
    mode: str,
    *,
    bucket_size: int = DEFAULT_BUCKET_SIZE,
    overlap: str = "off",
    chunks: int = 1,
) -> float:
    """Analytic per-device wire bytes for one gradient all-reduce.

    Bandwidth-optimal all-reduce moves ``2 * (n-1)/n * payload`` bytes
    through each device (reduce-scatter + all-gather, each ``(n-1)/n``);
    the mode sets the payload encoding:

      * exact: 4 bytes/element (fp32)
      * bf16:  2 bytes/element
      * int8:  1 byte/element + one fp32 scale per bucket (padding included,
        matching what :func:`grad_allreduce` actually ships)

    At the default bucket size int8 is ``4 / (1 + 4/1024)`` ≈ 3.98x smaller
    than exact — the microbenchmark (``scripts/allreduce_bench.py``) reports
    this next to measured ms/step.

    ``overlap="chunked"`` accounts the ring decomposition instead: each of
    the (up to) ``chunks`` pieces is padded to ``n`` segments (int8: to
    whole buckets per segment) and pays the same ``2 * (n-1)/n`` phase
    fraction on its padded payload — per-chunk padding is the only analytic
    cost of chunking, and it shrinks to zero at real gradient sizes.
    ``overlap="async"`` ships the exact same rings (the schedule, not the
    wire format, changes), so it shares the chunked accounting.
    """
    validate_mode(mode)
    validate_overlap(overlap, chunks if overlap != "off" else None)
    n = max(int(n_devices), 1)
    phase_fraction = 2.0 * (n - 1) / n
    if overlap != "off":
        total = 0.0
        for start, stop in _chunk_bounds(int(n_elements), int(chunks)):
            sz = stop - start
            if mode == "exact":
                total += 4.0 * (-(-sz // n) * n)
            elif mode == "bf16":
                total += 2.0 * (-(-sz // n) * n)
            else:
                nb = -(-sz // bucket_size)
                nb = -(-nb // n) * n
                total += float(nb * bucket_size) + 4.0 * nb
        return phase_fraction * total
    if mode == "exact":
        payload = 4.0 * n_elements
    elif mode == "bf16":
        payload = 2.0 * n_elements
    else:
        n_buckets = -(-int(n_elements) // bucket_size)  # ceil
        n_buckets = -(-n_buckets // n) * n  # pad bucket count to axis size
        payload = float(n_buckets * bucket_size) + 4.0 * n_buckets
    return phase_fraction * payload


def _quantize(x: jnp.ndarray, key: jax.Array) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Stochastic-rounding int8 quantization of (buckets, bucket_size) fp32.

    ``q = floor(x / scale + u)``, ``u ~ U[0, 1)``: E[q * scale] = x exactly,
    for any x — the rounding error is zero-mean noise, not bias. All-zero
    buckets (padding, dead layers) get scale 0 and quantize to 0.
    """
    amax = jnp.max(jnp.abs(x), axis=1)
    scale = amax / _QMAX
    safe = jnp.where(scale > 0.0, scale, 1.0)
    u = jax.random.uniform(key, x.shape, dtype=x.dtype)
    q = jnp.clip(jnp.floor(x / safe[:, None] + u), -_QMAX, _QMAX)
    return q.astype(jnp.int8), scale


def _int8_allreduce(
    flat: jnp.ndarray, axis_name: str, key: jax.Array, bucket_size: int
) -> jnp.ndarray:
    """Sum ``flat`` (fp32 vector) over ``axis_name`` with int8 wire format.

    Returns the fp32 vector of the same length; see the module docstring for
    the scatter / local-accumulate / gather decomposition.
    """
    n = jax.lax.axis_size(axis_name)
    n_elements = flat.shape[0]
    n_buckets = -(-n_elements // bucket_size)
    n_buckets = -(-n_buckets // n) * n
    padded = n_buckets * bucket_size
    x = jnp.zeros((padded,), flat.dtype).at[:n_elements].set(flat)
    x = x.reshape(n_buckets, bucket_size)

    q, scale = _quantize(x, key)

    # scatter: device d ends up holding every peer's quantized copy of
    # bucket range [d*chunk, (d+1)*chunk) — int8 on the wire, scales are the
    # only fp32 (one per bucket)
    chunk = n_buckets // n
    q_all = jax.lax.all_to_all(
        q.reshape(n, chunk, bucket_size), axis_name, split_axis=0, concat_axis=0
    )
    s_all = jax.lax.all_to_all(
        scale.reshape(n, chunk), axis_name, split_axis=0, concat_axis=0
    )

    # local dequant-accumulate: the deferred sum of the reduce-scatter
    reduced = jnp.sum(
        q_all.astype(flat.dtype) * s_all[:, :, None], axis=0
    )

    # gather: requantize the reduced chunk (fresh rounding noise from a
    # folded key) and all_gather it back as int8
    q2, s2 = _quantize(reduced, jax.random.fold_in(key, 1))
    q2_all = jax.lax.all_gather(q2, axis_name, axis=0, tiled=True)
    s2_all = jax.lax.all_gather(s2, axis_name, axis=0, tiled=True)

    out = q2_all.astype(flat.dtype) * s2_all[:, None]
    return out.reshape(-1)[:n_elements]


def _ring_chunk_allreduce(
    flat: jnp.ndarray,
    axis_name: str,
    mode: str,
    key: jax.Array | None,
    bucket_size: int,
) -> jnp.ndarray:
    """Sum one fp32 chunk over ``axis_name`` as an explicit ppermute ring.

    Reduce-scatter phase: hop t ships each device's running partial sum of
    one segment to the next ring neighbor (int8: requantized per hop in the
    bucket format — int8 partial sums would overflow and carry no shared
    scale); after n-1 hops device d owns the fully-reduced segment
    ``(d+1) % n``. All-gather phase: the owner's payload (int8 buckets +
    scales, or the raw wire-dtype segment) is forwarded VERBATIM around the
    ring, so every device dequantizes identical bytes and the result is
    bitwise identical across the axis. Returns the fp32 chunk of the input
    length.
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return flat
    n_elements = flat.shape[0]
    d = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    if mode == "int8":
        n_buckets = -(-n_elements // bucket_size)
        n_buckets = -(-n_buckets // n) * n
        seg = n_buckets // n
        x = jnp.zeros((n_buckets * bucket_size,), flat.dtype).at[:n_elements].set(flat)
        x = x.reshape(n, seg, bucket_size)
    else:
        wire_dtype = jnp.bfloat16 if mode == "bf16" else flat.dtype
        seg = -(-n_elements // n)
        x = jnp.zeros((n * seg,), flat.dtype).at[:n_elements].set(flat)
        x = x.reshape(n, seg).astype(wire_dtype)

    # reduce-scatter hops: acc starts as the local copy of segment d and
    # walks the ring accumulating each neighbor's contribution
    acc = jnp.take(x, d, axis=0)
    for t in range(n - 1):
        if mode == "int8":
            q, s = _quantize(acc, jax.random.fold_in(key, 2 + t))
            q = jax.lax.ppermute(q, axis_name, perm)
            s = jax.lax.ppermute(s, axis_name, perm)
            recv = q.astype(flat.dtype) * s[:, None]
        else:
            recv = jax.lax.ppermute(acc, axis_name, perm)
        acc = recv + jnp.take(x, (d - t - 1) % n, axis=0)

    # all-gather hops: the reduced segment owned here is quantized once
    # (fresh rounding noise, the same fold tag the single-shot gather uses)
    # and its bytes forwarded unchanged n-1 times
    owned = (d + 1) % n
    if mode == "int8":
        cur_q, cur_s = _quantize(acc, jax.random.fold_in(key, 1))
        out_q = jnp.zeros((n,) + cur_q.shape, cur_q.dtype).at[owned].set(cur_q)
        out_s = jnp.zeros((n,) + cur_s.shape, cur_s.dtype).at[owned].set(cur_s)
        for t in range(n - 1):
            cur_q = jax.lax.ppermute(cur_q, axis_name, perm)
            cur_s = jax.lax.ppermute(cur_s, axis_name, perm)
            idx = (owned - t - 1) % n
            out_q = out_q.at[idx].set(cur_q)
            out_s = out_s.at[idx].set(cur_s)
        out = out_q.astype(flat.dtype) * out_s[:, :, None]
    else:
        cur = acc
        out = jnp.zeros((n,) + acc.shape, acc.dtype).at[owned].set(acc)
        for t in range(n - 1):
            cur = jax.lax.ppermute(cur, axis_name, perm)
            out = out.at[(owned - t - 1) % n].set(cur)
        out = out.astype(flat.dtype)
    return out.reshape(-1)[:n_elements]


def grad_allreduce(
    grads,
    axis_name: str,
    mode: str = "exact",
    *,
    key: jax.Array | None = None,
    bucket_size: int = DEFAULT_BUCKET_SIZE,
    overlap: str = "off",
    chunks: int = DEFAULT_COMM_CHUNKS,
):
    """All-reduce (sum) a gradient pytree over ``axis_name``.

    Drop-in for ``jax.lax.psum(grads, axis_name)`` inside ``shard_map``.
    ``mode`` selects the wire format (:data:`GRAD_ALLREDUCE_MODES`); ``int8``
    requires ``key`` — the per-step PRNG key that makes the stochastic
    rounding unbiased AND reproducible (thread it from the train step's rng;
    under TP, fold with the data-axis index only so model-axis replicas
    round identically). Leaf dtypes and the pytree structure are preserved.

    ``overlap`` (:data:`COMM_OVERLAP_MODES`) picks the schedule: ``off`` is
    the single-shot fused path above, byte-for-byte unchanged; ``chunked``
    cuts the flattened gradient into ``chunks`` layer-ordered pieces and
    reduces each as an independent ppermute ring
    (:func:`_ring_chunk_allreduce`, per-chunk keys ``fold_in(key, c)``) so
    XLA's latency-hiding scheduler can overlap one chunk's wire hops with
    the next chunk's quant/dequant compute.
    """
    validate_mode(mode)
    validate_overlap(overlap, chunks if overlap != "off" else None)
    if overlap == "off":
        if mode == "exact":
            return jax.lax.psum(grads, axis_name)
        if mode == "bf16":
            return jax.tree.map(
                lambda g: jax.lax.psum(g.astype(jnp.bfloat16), axis_name).astype(g.dtype),
                grads,
            )
        if key is None:
            raise ValueError("grad_allreduce mode 'int8' requires a PRNG key")
        leaves, treedef = jax.tree.flatten(grads)
        if not leaves:
            return grads
        flat = jnp.concatenate([l.astype(jnp.float32).reshape(-1) for l in leaves])
        summed = _int8_allreduce(flat, axis_name, key, bucket_size)
        out, offset = [], 0
        for l in leaves:
            out.append(summed[offset:offset + l.size].reshape(l.shape).astype(l.dtype))
            offset += l.size
        return jax.tree.unflatten(treedef, out)

    if mode == "int8" and key is None:
        raise ValueError("grad_allreduce mode 'int8' requires a PRNG key")
    leaves, treedef = jax.tree.flatten(grads)
    if not leaves:
        return grads
    if overlap == "chunked":
        flat = jnp.concatenate([l.astype(jnp.float32).reshape(-1) for l in leaves])
        pieces = []
        for c, (start, stop) in enumerate(_chunk_bounds(flat.shape[0], chunks)):
            ck = jax.random.fold_in(key, c) if key is not None else None
            pieces.append(
                _ring_chunk_allreduce(flat[start:stop], axis_name, mode, ck, bucket_size)
            )
        summed = jnp.concatenate(pieces) if len(pieces) > 1 else pieces[0]
        out, offset = [], 0
        for l in leaves:
            out.append(summed[offset:offset + l.size].reshape(l.shape).astype(l.dtype))
            offset += l.size
        return jax.tree.unflatten(treedef, out)

    # async: the chunked branch's global concatenate makes EVERY ring depend
    # on ALL cotangents, which serializes the collective tail after the full
    # backward. Here each bucket (same _chunk_bounds boundaries over the same
    # leaf-order flat layout, same fold_in(key, c) schedule — so the reduced
    # values are identical to chunked, bitwise under int8) is assembled from
    # ONLY the leaf slices it spans, and the rings are issued in reverse
    # bucket order: under reverse-mode AD the LAST layers' cotangents
    # materialize first, so the tail buckets' rings are data-ready while the
    # first layers' backward matmuls are still running — genuine
    # data-independence for XLA's latency-hiding scheduler.
    offsets, off = [], 0
    for l in leaves:
        offsets.append(off)
        off += l.size
    bounds = _chunk_bounds(off, chunks)
    reduced = [None] * len(bounds)
    for c in reversed(range(len(bounds))):
        start, stop = bounds[c]
        parts = []
        for l, loff in zip(leaves, offsets):
            lo, hi = max(start, loff), min(stop, loff + l.size)
            if lo < hi:
                parts.append(
                    l.reshape(-1)[lo - loff:hi - loff].astype(jnp.float32)
                )
        bucket = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        ck = jax.random.fold_in(key, c) if key is not None else None
        reduced[c] = _ring_chunk_allreduce(bucket, axis_name, mode, ck, bucket_size)
    out = []
    for l, loff in zip(leaves, offsets):
        pieces = []
        for (start, stop), r in zip(bounds, reduced):
            lo, hi = max(start, loff), min(stop, loff + l.size)
            if lo < hi:
                pieces.append(r[lo - start:hi - start])
        if not pieces:
            out.append(l)  # zero-size leaf: nothing was reduced
            continue
        piece = jnp.concatenate(pieces) if len(pieces) > 1 else pieces[0]
        out.append(piece.reshape(l.shape).astype(l.dtype))
    return jax.tree.unflatten(treedef, out)
