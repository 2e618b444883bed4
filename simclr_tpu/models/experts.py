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
pair is sorted by expert, the pairs of experts held elsewhere last; the
held experts' rows then lie in ``held`` contiguous groups, and each of the
three products is one grouped matrix product over them. The buffer has a
row for every pair, which is what a router that sent every token to held
experts would fill, so shapes are static and no pair can be left behind
(there is nothing to count; ``tests/test_sequence_encoder.py`` holds the
result under a router that sends every token to two experts). On a TPU
the grouped product is ``jax.experimental.pallas.ops.tpu.megablox.gmm``
(JAX's kernel, with its own backward pass), which visits only the tiles
that hold rows of a group; elsewhere it is ``jax.lax.ragged_dot``. Rows
behind the last group are not written by the kernel, so both directions
mask them.
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take(x, order, inverse, repeats=1):
    """``repeat(x, repeats)[order]`` for a permutation ``order`` with its
    ``inverse``: the transpose of a gather by a permutation is the gather by
    its inverse (summed over a row's ``repeats`` copies), and a gather is
    what a TPU does well where the generic transpose, a scatter-add, runs
    row by row."""
    return x[order // repeats]


def _take_fwd(x, order, inverse, repeats=1):
    return x[order // repeats], inverse


def _take_bwd(repeats, inverse, g):
    g = g[inverse]
    return g.reshape(-1, repeats, g.shape[-1]).sum(axis=1), None, None


_take.defvjp(_take_fwd, _take_bwd)


def _tile(n: int, sizes=(512, 384, 256, 128)) -> int | None:
    """The largest of ``sizes`` that divides ``n``."""
    return next((t for t in sizes if n % t == 0), None)


def grouped_product(lhs, rhs, group_sizes, valid):
    """``lhs[rows of group g] @ rhs[g]`` for every group, rows in order of
    group; ``valid`` (M, 1) marks the rows inside a group. Invalid rows are
    zero in the result and take no gradient."""
    lhs = jnp.where(valid, lhs, 0)
    tm = _tile(lhs.shape[0], (512, 256, 128))
    if on_tpu() and tm is not None:
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        tiles = (
            tm, _tile(rhs.shape[1]) or 128,
            _tile(rhs.shape[2], (768, 512, 384, 256, 128)) or 128,
        )
        out = megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, tiles)
    else:
        out = jax.lax.ragged_dot(
            lhs, rhs, group_sizes, preferred_element_type=jnp.float32
        ).astype(lhs.dtype)
    return jnp.where(valid, out, 0)


def held_experts(m, weights, chosen, gate, up, down, first: int, dtype):
    """The held experts' part of the layer's result for tokens ``m`` (T, H),
    and ``load`` (held,) int32, the pairs each held expert took.

    ``weights``, ``chosen`` (T, per_token) come from :func:`route`; ``gate``,
    ``up`` (held, H, W) and ``down`` (held, W, H) are this chip's experts,
    numbered ``first .. first + held - 1`` in the router's outputs.
    """
    tokens, per_token = chosen.shape
    held = gate.shape[0]
    local = chosen - first
    local = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    order = jnp.argsort(local, stable=True)  # pairs of held experts first
    inverse = jnp.argsort(order)
    # by comparison: a bincount is a scatter-add, one update after another
    load = jnp.sum(local[:, None] == jnp.arange(held), axis=0, dtype=jnp.int32)
    rows = tokens * per_token
    valid = (jnp.arange(rows) < jnp.sum(load))[:, None]
    # each token's row once per pair, the pairs in order of expert
    x = _take(m.astype(dtype), order, inverse, per_token)
    hidden = jax.nn.relu(grouped_product(x, gate.astype(dtype), load, valid))
    hidden = hidden * grouped_product(x, up.astype(dtype), load, valid)
    y = grouped_product(hidden, down.astype(dtype), load, valid)
    y = _take(y, inverse, order).astype(jnp.float32)  # back to (token, pair)
    kept = (local < held).reshape(tokens, per_token)
    y = y.reshape(tokens, per_token, -1) * jnp.where(kept, weights, 0.0)[..., None]
    return jnp.sum(y, axis=1), load
