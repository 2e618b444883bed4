"""A routed expert layer that is told which experts it holds.

The router scores a token over ALL of the layer's experts, keeps the best
``per_token`` and normalises their weights over those it kept. This chip
holds ``held`` experts from ``first`` on (``models/arch.py``: one chip's
share of a layer divided over several) and computes their part of

    y = sum over a token's chosen experts e of  w_e * down_e(relu(gate_e m) * up_e m)

What the experts that live elsewhere would add is left out: on one chip the
layer runs without its exchange, and nothing here stands in for the absent
chips. The parts that all the holders of a layer compute add up to the
uncut layer's result (``tests/test_sequence_encoder.py``).

No token is dropped and no expert is capped. Every (token, chosen expert)
pair is sorted by expert, the pairs of experts held elsewhere last, so the
held experts' pairs are the first ``total = sum(load)`` positions of the
sorted order, in ``held`` contiguous groups. That range is cut into CHUNKS
of ``C`` rows (:func:`chunk_rows`: from shapes alone, a little over what an
even router sends to the held experts), and as many chunks run as hold a
held pair: ``ceil(total / C)``, which the program reads from the router at
run time (a loop of that many turns, not a branch per chunk, so a chunk
that does not run costs nothing). A turn gathers its ``C`` rows of the
tokens, runs the three grouped matrix products over them and adds each
token's weighted rows to the result in float32. The static number of
chunks is ``tokens * per_token / C``, which is what a router that sent
every token to held experts would fill, so shapes are static and no pair
can be left behind (``tests/test_sequence_encoder.py`` holds the result
under a router that sends every token to two experts). An expert whose rows
lie across a chunk's edge is computed in two parts, and its weights'
gradients add.

The backward pass is the layer's own (:func:`_held`, a ``custom_vjp``): it
keeps the tokens, the routing and the sorted order, runs the same chunks
again, rebuilds a chunk's gathered rows and products there and adds up the
gradients in float32. Rows move by gathers in both directions (the
transpose of a gather by a permutation is the gather by its inverse, never
a scatter-add, which a TPU runs row by row).

On a TPU the grouped product is
``jax.experimental.pallas.ops.tpu.megablox.gmm`` (JAX's kernel, with its own
backward pass), which visits only the tiles that hold rows of a group;
elsewhere it is ``jax.lax.ragged_dot``. Rows of the last chunk behind the
last group are not written by the kernel: the 768-wide results are masked,
and of the model-wide ones only held pairs' rows are ever read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from simclr_tpu.models.attention import on_tpu


def route(probs, per_token: int):
    """The ``per_token`` largest of ``probs`` (T, experts) and their indices,
    the weights normalised to sum to one over the chosen."""
    weights, chosen = jax.lax.top_k(probs, per_token)
    return weights / jnp.sum(weights, axis=-1, keepdims=True), chosen


# The grouped product's row tile on a TPU, and what a chunk holds: the
# smallest multiple of that tile which divides the view's pairs and is at
# least CHUNK_SHARE (a ratio) of what an even router sends the held experts.
# The reading that set it (chip run, PR 35: smallthinker-21ba3b-enc, 16 held
# of 64, 5 seeds x 12 steps x 8 layer-calls): a call's held pairs were 9,335
# to 44,682 against an even 24,576; 2.9% of the calls (0 to 5.2% by seed,
# all in layers 2 and 3) held more than 4/3 of it and ran two chunks, none
# more than twice it. At 4/3 at least nine calls in ten fit one chunk, which
# is the rule; twice would make every call's one chunk half as large again.
ROW_TILE = 512
CHUNK_SHARE = (4, 3)


def chunk_rows(rows: int, held: int, experts: int) -> int:
    """Rows of one chunk of the sorted (token, expert) pairs, ``rows`` in
    all, on a chip that holds ``held`` of ``experts``: from shapes alone. All
    ``rows`` (one chunk, every pair in it) where nothing smaller fits."""
    share, of = CHUNK_SHARE
    return next(
        (
            c for c in range(ROW_TILE, rows, ROW_TILE)
            if rows % c == 0 and c * of * experts >= share * rows * held
        ),
        rows,
    )


def _tile(n: int, sizes=(512, 384, 256, 128)) -> int | None:
    """The largest of ``sizes`` that divides ``n``."""
    return next((t for t in sizes if n % t == 0), None)


def grouped_product(lhs, rhs, group_sizes):
    """``lhs[rows of group g] @ rhs[g]`` for every group, rows in order of
    group. Rows behind the last group belong to none: the TPU's kernel leaves
    them unwritten, ``ragged_dot`` zero."""
    tm = _tile(lhs.shape[0], (ROW_TILE, 256, 128))
    if on_tpu() and tm is not None:
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        tiles = (
            tm, _tile(rhs.shape[1]) or 128,
            _tile(rhs.shape[2], (768, 512, 384, 256, 128)) or 128,
        )
        return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, tiles)
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes, preferred_element_type=jnp.float32
    ).astype(lhs.dtype)


def _chunk_of(load, order, per_token: int, start, chunk: int):
    """The ``chunk`` sorted pairs from ``start`` on: each one's number
    ``token * per_token + choice``, its token, the rows each held expert has
    among them (its group cut at the chunk's edges) and ``valid`` (chunk, 1),
    the rows that hold a held expert's pair."""
    ends = jnp.cumsum(load)
    sizes = jnp.clip(ends - start, 0, chunk) - jnp.clip(ends - load - start, 0, chunk)
    valid = (start + jnp.arange(chunk) < ends[-1])[:, None]
    pairs = jax.lax.dynamic_slice(order, (start,), (chunk,))
    return pairs, pairs // per_token, sizes, valid


def _products(x, gate, up, down, sizes, valid):
    """``down(relu(gate x) * up x)`` by groups of rows, for one chunk."""
    hidden = jax.nn.relu(jnp.where(valid, grouped_product(x, gate, sizes), 0))
    hidden = hidden * jnp.where(valid, grouped_product(x, up, sizes), 0)
    return grouped_product(hidden, down, sizes)


def _to_tokens(rows, place, start, total, scale=None):
    """For every token the float32 sum of its pairs' rows (times ``scale``)
    among ``rows``, which are the sorted positions from ``start`` on; a pair
    outside them, or of an expert held elsewhere (``place >= total``), adds
    nothing. ``place``, ``scale`` (tokens, per_token). A gather per choice,
    summed as they come, so that no (token, pair) array of rows is written;
    a select and not a product by zero, because rows behind the last group
    hold whatever the kernel left there."""
    at = place - start
    live = (at >= 0) & (at < rows.shape[0]) & (place < total)
    out = 0.0
    for p in range(place.shape[1]):
        row = rows[jnp.clip(at[:, p], 0, rows.shape[0] - 1)].astype(jnp.float32)
        if scale is not None:
            row = row * scale[:, p, None]
        out = out + jnp.where(live[:, p, None], row, 0.0)
    return out


def chunks_run(total, chunk: int):
    """The chunks that hold one of ``total`` held pairs."""
    return (total + chunk - 1) // chunk


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _held(m, weights, gate, up, down, order, place, load, chunk, dtype):
    """The held experts' weighted sum for tokens ``m`` (float32), chunk by
    chunk of the sorted pairs: ``order`` (pairs,) is the pair at each sorted
    position, ``place`` (tokens, per_token) its inverse, ``load`` (held,) the
    groups' sizes."""
    return _held_fwd(m, weights, gate, up, down, order, place, load, chunk, dtype)[0]


def _held_fwd(m, weights, gate, up, down, order, place, load, chunk, dtype):
    total = jnp.sum(load)
    x, experts = m.astype(dtype), [w.astype(dtype) for w in (gate, up, down)]

    def turn(i, out):
        start = i * chunk
        _, tokens, sizes, valid = _chunk_of(load, order, place.shape[1], start, chunk)
        y = _products(x[tokens], *experts, sizes, valid)
        return out + _to_tokens(y, place, start, total, weights)

    out = jax.lax.fori_loop(
        0, chunks_run(total, chunk), turn, jnp.zeros(m.shape, jnp.float32)
    )
    return out, (m, weights, gate, up, down, order, place, load)


def _held_bwd(chunk, dtype, kept, dout):
    m, weights, gate, up, down, order, place, load = kept
    total = jnp.sum(load)
    x, experts = m.astype(dtype), [w.astype(dtype) for w in (gate, up, down)]

    def turn(i, carry):
        dm, dexperts, dscale = carry
        start = i * chunk
        pairs, tokens, sizes, valid = _chunk_of(load, order, place.shape[1], start, chunk)
        # the chunk's rows and products again: nothing of them was kept
        y, back = jax.vjp(
            lambda rows, *w: _products(rows, *w, sizes, valid), x[tokens], *experts
        )
        dy = dout[tokens]  # each pair's row of the result's cotangent, float32
        dscale = jax.lax.dynamic_update_slice(
            dscale, jnp.sum(dy * y.astype(jnp.float32), axis=-1), (start,)
        )
        dy = dy * weights.reshape(-1)[pairs][:, None]
        dx, *dw = back(dy.astype(dtype))
        dm = dm + _to_tokens(dx, place, start, total)
        return dm, [a + b.astype(jnp.float32) for a, b in zip(dexperts, dw)], dscale

    dm, dexperts, dscale = jax.lax.fori_loop(
        0, chunks_run(total, chunk), turn,
        (
            jnp.zeros(m.shape, jnp.float32),
            [jnp.zeros(w.shape, jnp.float32) for w in experts],
            jnp.zeros(order.shape, jnp.float32),
        ),
    )
    dweights = jnp.where(place < total, dscale[place], 0.0)
    return (
        dm.astype(m.dtype), dweights.astype(weights.dtype),
        *(d.astype(w.dtype) for d, w in zip(dexperts, (gate, up, down))),
        None, None, None,
    )


_held.defvjp(_held_fwd, _held_bwd)


def held_experts(m, weights, chosen, gate, up, down, first: int, experts: int, dtype):
    """The held experts' part of the layer's result for tokens ``m`` (T, H);
    ``load`` (held,) int32, the pairs each held expert took; and the chunks
    of the sorted pairs that ran (0 where no pair was a held expert's).

    ``weights``, ``chosen`` (T, per_token) come from :func:`route` over
    ``experts`` experts; ``gate``, ``up`` (held, H, W) and ``down``
    (held, W, H) are this chip's, numbered ``first .. first + held - 1`` in
    the router's outputs. Products take ``dtype`` inputs.
    """
    tokens, per_token = chosen.shape
    held = gate.shape[0]
    local = chosen - first
    local = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    order = jnp.argsort(local, stable=True)  # pairs of held experts first
    place = jnp.argsort(order).reshape(tokens, per_token)
    # by comparison: a bincount is a scatter-add, one update after another
    load = jnp.sum(local[:, None] == jnp.arange(held), axis=0, dtype=jnp.int32)
    chunk = chunk_rows(tokens * per_token, held, experts)
    y = _held(m, weights, gate, up, down, order, place, load, chunk, dtype)
    return y, load, chunks_run(jnp.sum(load), chunk)
