"""Grouped-query causal attention over packed rows of documents.

A row holds several documents end to end (``data/documents.py``); a token
attends to the tokens before it in its own document (``segments``), and on a
windowed layer only to the last ``window`` of them, itself included. Layers
with rotary embedding turn q and k by the token's position inside its
document before this is called (:func:`rotary`).

Two implementations of the same sum. On a TPU, rows that the kernel's
blocks tile go through ``jax.experimental.pallas.ops.tpu.splash_attention``
(JAX's own kernel, not written here): it never holds a row's scores, skips
the blocks that the causal and window masks empty, and brings its backward
pass. Everywhere else (the CPU tests' toy rows) the scores are formed whole
in float32. The choice reads the backend and the shapes, nothing of the
configuration.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

KERNEL_BLOCK = 512  # q and kv block of the kernel, forward and backward


def on_tpu() -> bool:
    """Whether the compiled-kernel paths of the sequence encoder apply (the
    attention here, the grouped products of ``models/experts.py``)."""
    return jax.default_backend() == "tpu"


def rotary(x, positions, theta: float):
    """Rotary position embedding of ``x`` (..., L, heads, D) at integer
    ``positions`` (..., L): the half-split form (pairs ``(i, i + D/2)``),
    frequencies ``theta ** (-2i / D)``, angles in float32."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., L, D/2)
    cos, sin = jnp.cos(angles)[..., None, :], jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return turned.astype(x.dtype)


def allowed(segments, window: int | None):
    """(B, L, L) bool: query l may see key m."""
    idx = jnp.arange(segments.shape[-1])
    ok = (idx[:, None] >= idx[None, :]) & (
        segments[:, :, None] == segments[:, None, :]
    )
    if window is not None:
        ok &= idx[:, None] - idx[None, :] < window
    return ok


def _dense(q, k, v, segments, window):
    b, l, heads, d = q.shape
    kv = k.shape[2]
    q = q.reshape(b, l, kv, heads // kv, d)
    scores = jnp.einsum(
        "blkgd,bmkd->bkglm", q, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(allowed(segments, window)[:, None, None], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum(
        "bkglm,bmkd->blkgd", weights, v, preferred_element_type=jnp.float32
    )
    return out.reshape(b, l, heads, d).astype(q.dtype)


@functools.lru_cache(maxsize=8)
def _kernel(length: int, group: int, window: int | None):
    """The splash multi-query kernel for ``group`` query heads on one
    key-value head over rows of ``length``; its mask tables are built once
    per shape (host work of a second or two at 8,192)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
        splash_attention_mask as masks,
    )

    shape = (length, length)
    if window is None:
        mask = masks.CausalMask(shape)
    else:  # keys l - window + 1 .. l
        mask = masks.LocalMask(shape, window_size=(window - 1, 0), offset=0)
    block = min(KERNEL_BLOCK, length)
    sizes = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block,
    )
    return splash.make_splash_mqa_single_device(
        masks.MultiHeadMask([mask] * group), block_sizes=sizes
    )


def _splash(q, k, v, segments, window):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
    )

    b, l, heads, d = q.shape
    kv = k.shape[2]
    # the tables are constants of the program, not values of this trace
    with jax.ensure_compile_time_eval():
        kernel = _kernel(l, heads // kv, window)
    q = (q / jnp.sqrt(jnp.float32(d)).astype(q.dtype)).reshape(b, l, kv, heads // kv, d)
    q = q.transpose(0, 2, 3, 1, 4)  # (B, KV, G, L, D)
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)  # (B, KV, L, D)
    per_head = jax.vmap(kernel, in_axes=(0, 0, 0, None))  # over key-value heads
    per_row = jax.vmap(per_head, in_axes=(0, 0, 0, 0))
    out = per_row(q, k, v, splash.SegmentIds(q=segments, kv=segments))
    return out.transpose(0, 3, 1, 2, 4).reshape(b, l, heads, d)


def attention(q, k, v, segments, window: int | None = None):
    """``softmax(q k^T / sqrt(D) + mask) v`` with ``q`` (B, L, heads, D) and
    ``k``, ``v`` (B, L, kv_heads, D), ``heads`` a multiple of ``kv_heads``
    (query head ``h`` reads key-value head ``h // (heads / kv_heads)``);
    ``segments`` (B, L) int32 names each token's document."""
    length, d = q.shape[1], q.shape[-1]
    if on_tpu() and d % 128 == 0 and length % 128 == 0 and (
        length <= KERNEL_BLOCK or length % KERNEL_BLOCK == 0
    ):
        return _splash(q, k, v, segments, window)
    return _dense(q, k, v, segments, window)
