"""A decoder-style token encoder with routed experts, as a document encoder.

The block of PowerInfer's SmallThinker family (``models/arch.py`` holds the
shapes): with ``h`` a layer's input,

    p     = softmax(h W_r)                 the router reads the layer's INPUT
    w, e  = the best ``experts_per_token`` of p, w normalised over them
    a     = RMSNorm(h);  q, k, v = a W_q, a W_k, a W_v   (grouped-query heads)
    layout 0:  no rotary embedding, causal attention over the whole document
    layout 1:  rotary embedding on q and k, causal over the last ``window``
    h1    = h + attention(q, k, v) W_o
    out   = h1 + sum over e of w_e * down_e(relu(gate_e m) * up_e m),  m = RMSNorm(h1)

No bias anywhere, no shared expert, no language-model head: the contrastive
objective has none. The encoder's output is one row per document, the mean
of the final RMS-normed hidden states over the document's tokens
(:func:`pool_segments`), which is what ``models/heads.py`` and NT-Xent take.

Precision: parameters float32; every large product takes ``dtype`` inputs
(bfloat16) and accumulates in float32; the residual stream, the norms, the
router's product and softmax, and the pooling stay float32, so that the
choice of experts does not hang on bfloat16 rounding of 64 near-equal
scores. Each layer is rematerialised in the backward pass (``nn.remat``):
at 16,384 tokens a view the layers' insides would not fit beside a
half-billion parameters' gradients and momentum, so this is part of the
encoder and not an option of the run.

Counters: the encoder sows into the ``counters`` collection, for this call,
``expert_load`` (layers, held experts), the pairs each held expert took;
``expert_chunks`` (layers,), the chunks of sorted pairs each layer's expert
loop ran (``models/experts.py``: a chunk is a fixed number of rows of the
pairs sorted by expert, and as many run as hold a held expert's pair); and
``expert_calls`` (layers,), a one for the call, so that whatever sums the
leaves over views and devices also counts the calls it summed. The step
hands them to the host with the loss.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from simclr_tpu.models.arch import SequenceEncoderSpec
from simclr_tpu.models.attention import attention, rotary
from simclr_tpu.models.experts import held_experts, route
from simclr_tpu.obs.trace import (
    SCOPE_ATTENTION,
    SCOPE_EXPERTS,
    SCOPE_MOE,
    SCOPE_ROUTER,
)

Dtype = Any
# the family's ``initializer_range``
_init = nn.initializers.normal(0.02)


class PackedView(NamedTuple):
    """One view of a batch of documents, packed into rows: token ids, each
    token's document within its row (0 .. documents a row - 1, in order) and
    its position inside that document, all (rows, row length) int32; and the
    documents' lengths, (rows, documents a row) int32."""

    tokens: jax.Array
    segments: jax.Array
    positions: jax.Array
    lengths: jax.Array


def rms_norm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def pool_segments(h, segments, lengths):
    """Mean of ``h`` (rows, L, H) over each document's tokens: (rows *
    documents a row, H), documents in row order."""
    member = jax.nn.one_hot(segments, lengths.shape[-1], dtype=jnp.float32)
    sums = jnp.einsum(
        "rls,rlh->rsh", member, h.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    means = sums / jnp.maximum(lengths, 1)[..., None].astype(jnp.float32)
    return means.reshape(-1, h.shape[-1])


class SequenceLayer(nn.Module):
    spec: SequenceEncoderSpec
    windowed: bool  # layout 1: rotary embedding and the window
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, h, segments, positions):
        s, dt = self.spec, self.dtype
        rows, length, width = h.shape

        def matrix(name, *shape):
            return self.param(name, _init, shape, jnp.float32)

        def scale(name):
            return self.param(name, nn.initializers.ones, (width,), jnp.float32)

        with jax.named_scope(SCOPE_MOE), jax.named_scope(SCOPE_ROUTER):
            logits = jnp.dot(
                h.reshape(-1, width), matrix("router", width, s.experts),
                precision=jax.lax.Precision.HIGHEST,
            )
            weights, chosen = route(jax.nn.softmax(logits, axis=-1), s.experts_per_token)

        with jax.named_scope(SCOPE_ATTENTION):
            a = rms_norm(h, scale("attention_norm"), s.rms_eps).astype(dt)

            def heads(name, n):
                y = jnp.dot(a, matrix(name, width, n * s.head_dim).astype(dt))
                return y.reshape(rows, length, n, s.head_dim)

            q, k, v = heads("q", s.heads), heads("k", s.kv_heads), heads("v", s.kv_heads)
            if self.windowed:
                q, k = (rotary(x, positions, s.rope_theta) for x in (q, k))
            out = attention(q, k, v, segments, s.window if self.windowed else None)
            out = jnp.dot(
                out.reshape(rows, length, -1),
                matrix("o", s.heads * s.head_dim, width).astype(dt),
                preferred_element_type=jnp.float32,
            )
            h = h + out

        with jax.named_scope(SCOPE_MOE), jax.named_scope(SCOPE_EXPERTS):
            m = rms_norm(h, scale("experts_norm"), s.rms_eps)
            y, load, chunks = held_experts(
                m.reshape(-1, width), weights, chosen,
                matrix("gate", s.experts_held, width, s.expert_width),
                matrix("up", s.experts_held, width, s.expert_width),
                matrix("down", s.experts_held, s.expert_width, width),
                s.first_expert, s.experts, dt,
            )
        return h + y.reshape(rows, length, width), (load, chunks)


class SequenceEncoder(nn.Module):
    """Packed token rows -> one feature row per document (float32)."""

    spec: SequenceEncoderSpec
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, view: PackedView, train: bool = True):
        del train  # no layer here behaves differently in training
        s = self.spec
        embedding = self.param("embedding", _init, (s.vocab_held, s.hidden), jnp.float32)
        h, counted = embedding[view.tokens], []
        for i, kind in enumerate(s.layout):
            h, counts = nn.remat(SequenceLayer)(s, bool(kind), self.dtype, name=f"layer_{i}")(
                h, view.segments, view.positions
            )
            counted.append(counts)
        loads, chunks = (jnp.stack(c) for c in zip(*counted))
        for name, leaf in (
            ("expert_load", loads), ("expert_chunks", chunks),
            ("expert_calls", jnp.ones_like(chunks)),
        ):
            self.sow(  # this call's numbers, not a history
                "counters", name, leaf, reduce_fn=lambda _, new: new, init_fn=lambda: None
            )
        scale = self.param("final_norm", nn.initializers.ones, (s.hidden,), jnp.float32)
        return pool_segments(rms_norm(h, scale, s.rms_eps), view.segments, view.lengths)
