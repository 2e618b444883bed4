"""Token documents: a seeded synthetic corpus, and the two views of a batch.

Beside ``data/cifar.py``: the source a sequence encoder trains on. There is
no network here and no tokenizer, so the corpus is drawn from a fixed
generator, the same in every run (as the synthetic CIFAR is): ``documents``
rows of ``tokens`` ids over the held slice of a vocabulary. An id is drawn
from a Zipf law over the slice (``p(i) ~ (i + 1) ** -exponent``), except
that a share ``TILT`` of a document's tokens come from that document's own
small set of ``TOPIC_IDS`` ids. The tilt is what there is to learn: two
spans of one document share more ids than spans of two documents do, by
little, so an untrained encoder reads NT-Xent's ln(2N-1).

The exponent is 0.5, flatter than a natural corpus's ~1. The expert layer
(``models/experts.py``) computes only the pairs routed to the experts it
holds, and the first layer routes by token id alone: under exponent 1 the
commonest id is 9% of all tokens and goes to the same six experts
throughout a run, so the held experts' work would swing by some 8% from seed
to seed (a seed draws the router) and the step time with it. Under 0.5 the
commonest id is 0.26% and the swing is under 1% of that layer's expert work.

The views (:func:`span_crop_views`, on the device, inside the step's
``augment`` scope) are what augmentation is for text: each document gives
one span crop per view, at a seeded offset, and a view's crops are packed
end to end into rows with no padding. The crops of a row take their
lengths from one fixed multiset (``crop_lengths``, a seeded permutation of
it per row and view), so every step of every seed has the same shapes and
the same number of tokens, short queries beside long passages.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from simclr_tpu.data.cifar import Dataset
from simclr_tpu.models.sequence import PackedView

DATASET_NAME = "synthetic-docs"
ZIPF_EXPONENT = 0.5
TOPIC_IDS = 64
TILT = 0.05


def synthetic_documents(
    documents: int, tokens: int, vocab: int, seed: int = 0
) -> Dataset:
    """``Dataset`` whose ``images`` are (documents, tokens) int32 ids below
    ``vocab`` (the loader's field name stays: the pipeline gathers rows and
    does not care what a row is) and whose labels are the row numbers."""
    rng = np.random.default_rng(seed)
    p = (np.arange(vocab, dtype=np.float64) + 1.0) ** -ZIPF_EXPONENT
    cdf = np.cumsum(p / p.sum())
    ids = np.searchsorted(cdf, rng.random((documents, tokens)), side="right")
    ids = np.minimum(ids, vocab - 1).astype(np.int32)
    topics = rng.integers(0, vocab, size=(documents, TOPIC_IDS), dtype=np.int32)
    own = topics[
        np.arange(documents)[:, None], rng.integers(0, TOPIC_IDS, size=ids.shape)
    ]
    ids = np.where(rng.random(ids.shape) < TILT, own, ids)
    return Dataset(
        images=ids, labels=np.arange(documents, dtype=np.int32),
        name=DATASET_NAME, split="train", synthetic=True,
    )


def _pack_row(keys, docs, lengths):
    """One row of one view: ``docs`` (D, T) ids, a key per document."""
    per_row, doc_tokens = docs.shape
    perm = jax.random.permutation(jax.random.fold_in(keys[0], per_row), per_row)
    lens = jnp.asarray(lengths)[perm]  # document s of the row gives a crop of lens[s]
    starts = jnp.cumsum(lens) - lens
    offsets = jax.vmap(
        lambda key, n: jax.random.randint(key, (), 0, doc_tokens - n + 1)
    )(keys, lens)
    at = jnp.arange(int(np.sum(lengths)), dtype=jnp.int32)
    segment = (jnp.searchsorted(starts, at, side="right") - 1).astype(jnp.int32)
    position = at - starts[segment]
    return PackedView(
        docs[segment, offsets[segment] + position], segment, position,
        lens.astype(jnp.int32),
    )


def span_crop_views(keys, docs, crop_lengths: tuple[int, ...]):
    """Two :class:`PackedView` of ``docs`` (n, T) int32, one span crop per
    document and view. ``keys`` (2n,) are the step's per-sample keys, view 0's
    first (``parallel/steps._global_sample_keys``: a function of a
    document's place in the global batch, not of the mesh). Row ``r`` of a
    view packs documents ``r * D .. r * D + D - 1`` in order, ``D =
    len(crop_lengths)``; its lengths are a permutation of ``crop_lengths``
    drawn from its first document's key."""
    n, doc_tokens = docs.shape
    per_row = len(crop_lengths)
    if n % per_row:
        raise ValueError(
            f"{n} documents a device and step do not fill rows of {per_row} "
            f"crops (experiment.crop_lengths): make experiment.batches a multiple"
        )
    if max(crop_lengths) > doc_tokens:
        raise ValueError(
            f"a crop of {max(crop_lengths)} tokens from documents of {doc_tokens}"
        )
    lengths = np.asarray(crop_lengths, dtype=np.int32)
    rows = jax.vmap(_pack_row, in_axes=(0, 0, None))

    def view(view_keys):
        return rows(
            view_keys.reshape(n // per_row, per_row),
            docs.reshape(n // per_row, per_row, doc_tokens), lengths,
        )

    return view(keys[:n]), view(keys[n:])
