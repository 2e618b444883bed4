"""The sequence encoder (models/sequence.py, attention.py, experts.py) and the
document views (data/documents.py) on the CPU at toy sizes, in float32,
against the benchmark's plain reference (benchmarks/references/
smallthinker_simclr.py): hidden 64, one period of four layers, window 8 on
rows of 64 tokens with one crop of 24, 8 experts of which 2, 4 and all are
held, 2 a token. The weights are drawn wider than the encoder's own 0.02 so
that attention is far from uniform and routing far from even: a part wired
otherwise then reads 1e-2 and more where the two sides agree to 1e-5.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.references import smallthinker_simclr as ref  # noqa: E402
from simclr_tpu.data.documents import span_crop_views, synthetic_documents  # noqa: E402
from simclr_tpu.models import experts  # noqa: E402
from simclr_tpu.models.arch import SEQUENCE_ENCODERS  # noqa: E402
from simclr_tpu.models.contrastive import ContrastiveModel  # noqa: E402
from simclr_tpu.models.sequence import PackedView, SequenceEncoder, pool_segments  # noqa: E402
from simclr_tpu.ops.ntxent import ntxent_loss  # noqa: E402

TINY = SEQUENCE_ENCODERS["smallthinker-tiny"]
CROPS = (24, 16, 8, 8, 4, 2, 1, 1)
SHAPE = {
    "layout": TINY.layout, "heads": TINY.heads, "kv_heads": TINY.kv_heads,
    "head_dim": TINY.head_dim, "window": TINY.window, "theta": TINY.rope_theta,
    "eps": TINY.rms_eps, "per_token": TINY.experts_per_token, "first": 0,
}


@pytest.fixture(scope="module")
def views():
    docs = synthetic_documents(16, 128, TINY.vocab_held)
    keys = jax.random.split(jax.random.key(3), 32)
    return docs, keys, span_crop_views(keys, jnp.asarray(docs.images), CROPS)


def widened(params, key):
    """The drawn parameters with every matrix five times as wide (norm
    scales jittered): attention and routing that differ from token to token."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        p * 5.0 if p.ndim > 1 else p + 0.3 * jax.random.normal(k, p.shape)
        for p, k in zip(leaves, keys)
    ])


def model_and_variables(held: int, first: int, view, monkeypatch=None):
    name = f"tiny-h{held}-f{first}"
    SEQUENCE_ENCODERS[name] = dataclasses.replace(TINY, experts_held=held, first_expert=first)
    model = ContrastiveModel(base_cnn=name, dtype=jnp.float32)
    variables = model.init(jax.random.key(11), view, train=True)
    params = widened(variables["params"], jax.random.key(12))
    return model, params, variables["batch_stats"]


def program_loss(model, params, stats, v0, v1):
    z0, mut = model.apply({"params": params, "batch_stats": stats}, v0, train=True, mutable=["batch_stats"])
    z1, _ = model.apply({"params": params, "batch_stats": mut["batch_stats"]}, v1, train=True, mutable=["batch_stats"])
    return ntxent_loss(z0, z1, temperature=0.5), (z0, z1)


def reference_loss(params, stats, v0, v1, first=0, fault=None):
    shape = {**SHAPE, "first": first}
    z0, stats, _ = ref.embed(shape, params, stats, tuple(v0), None, fault)
    z1, _, _ = ref.embed(shape, params, stats, tuple(v1), None, fault)
    return ref.nt_xent(z0, z1, 0.5), (z0, z1)


def worst_gap(a, b):
    """The widest gap of any leaf, against that leaf's largest entry (or 1e-4:
    the head's bias in front of its batch norm has no gradient but rounding)."""
    return max(
        float(jnp.max(jnp.abs(x - y)) / (jnp.max(jnp.abs(y)) + 1e-4))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


@pytest.mark.parametrize("held,first", [(2, 0), (2, 6), (4, 4), (8, 0)])
def test_forward_loss_and_gradients_agree_with_the_plain_reference(views, held, first):
    _, _, (v0, v1) = views
    model, params, stats = model_and_variables(held, first, v0)
    with jax.default_matmul_precision("highest"):
        (ours, (z0, _)), g_ours = jax.value_and_grad(
            lambda p: program_loss(model, p, stats, v0, v1), has_aux=True)(params)
        (theirs, (r0, _)), g_theirs = jax.value_and_grad(
            lambda p: reference_loss(p, stats, v0, v1, first), has_aux=True)(params)
    np.testing.assert_allclose(z0, r0, rtol=2e-4, atol=2e-5)
    assert abs(float(ours) - float(theirs)) < 1e-5
    assert worst_gap(g_ours, g_theirs) < 1e-4


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_part_of_the_layer_moves_the_result_when_it_is_changed(views, fault):
    """The window, the layer without rotary embedding, the router's place and
    the renormalised weights: the reference with that part changed lies far
    from the program, so the agreement above is a test of each."""
    _, _, (v0, v1) = views
    model, params, stats = model_and_variables(4, 0, v0)
    with jax.default_matmul_precision("highest"):
        _, (z0, _) = program_loss(model, params, stats, v0, v1)
        _, (sound, _) = reference_loss(params, stats, v0, v1)
        _, (changed, _) = reference_loss(params, stats, v0, v1, fault=fault)
    agree = float(jnp.max(jnp.abs(z0 - sound)))
    apart = float(jnp.max(jnp.abs(z0 - changed)))
    assert agree < 1e-4 and apart > 100 * max(agree, 1e-6), (fault, agree, apart)


def expert_inputs(tokens=96, skew=False):
    k = jax.random.split(jax.random.key(5), 6)
    m = jax.random.normal(k[0], (tokens, TINY.hidden))
    logits = 2.0 * jax.random.normal(k[1], (tokens, TINY.experts))
    if skew:  # every token's two best are experts 0 and 1
        logits = logits.at[:, :2].add(50.0)
    weights, chosen = experts.route(jax.nn.softmax(logits), TINY.experts_per_token)
    gate = jax.random.normal(k[2], (TINY.experts, TINY.hidden, TINY.expert_width)) * 0.2
    up = jax.random.normal(k[3], (TINY.experts, TINY.hidden, TINY.expert_width)) * 0.2
    down = jax.random.normal(k[4], (TINY.experts, TINY.expert_width, TINY.hidden)) * 0.2
    return m, weights, chosen, gate, up, down


def uncut(m, weights, chosen, gate, up, down):
    """Every token through every chosen expert, one expert at a time."""
    out = np.zeros(m.shape, np.float64)
    m, weights, chosen, gate, up, down = (np.asarray(x, np.float64) for x in (m, weights, chosen, gate, up, down))
    for t in range(m.shape[0]):
        for w, e in zip(weights[t], chosen[t].astype(int)):
            out[t] += w * (np.maximum(m[t] @ gate[e], 0) * (m[t] @ up[e])) @ down[e]
    return out


@pytest.mark.parametrize("holders", [1, 2, 4])
def test_the_holders_shares_add_up_to_the_uncut_layer(holders):
    m, weights, chosen, gate, up, down = expert_inputs()
    held = TINY.experts // holders
    total, pairs = 0.0, 0
    for first in range(0, TINY.experts, held):
        part = slice(first, first + held)
        y, load, _ = experts.held_experts(
            m, weights, chosen, gate[part], up[part], down[part], first, TINY.experts, jnp.float32)
        share = ref.held_share(m, weights, chosen, gate[part], up[part], down[part], first, None)
        np.testing.assert_allclose(y, share, rtol=1e-4, atol=1e-5)
        total, pairs = total + y, pairs + int(load.sum())
    assert pairs == m.shape[0] * TINY.experts_per_token  # every pair, once
    np.testing.assert_allclose(total, uncut(m, weights, chosen, gate, up, down), rtol=1e-4, atol=1e-5)


def test_no_token_is_dropped_under_a_skewed_router():
    m, weights, chosen, gate, up, down = expert_inputs(skew=True)
    y, load, _ = experts.held_experts(
        m, weights, chosen, gate[:2], up[:2], down[:2], 0, TINY.experts, jnp.float32)
    assert load.tolist() == [m.shape[0], m.shape[0]]  # all 192 pairs on two experts
    np.testing.assert_allclose(y, uncut(m, weights, chosen, gate, up, down), rtol=1e-4, atol=1e-5)
    # and the other holders get nothing to do, without a shape changing
    y, load, chunks = experts.held_experts(
        m, weights, chosen, gate[2:4], up[2:4], down[2:4], 2, TINY.experts, jnp.float32)
    assert load.tolist() == [0, 0] and float(jnp.abs(y).max()) == 0.0 and int(chunks) == 0


def test_the_experts_gradients_match_the_dense_sum():
    m, weights, chosen, gate, up, down = expert_inputs(tokens=32)

    def ours(m, weights, gate, up, down):
        return jnp.sum(jnp.sin(experts.held_experts(
            m, weights, chosen, gate[2:6], up[2:6], down[2:6], 2, TINY.experts, jnp.float32)[0]))

    def theirs(m, weights, gate, up, down):
        return jnp.sum(jnp.sin(ref.held_share(m, weights, chosen, gate[2:6], up[2:6], down[2:6], 2, None)))

    with jax.default_matmul_precision("highest"):
        a = jax.grad(ours, argnums=(0, 1, 2, 3, 4))(m, weights, gate, up, down)
        b = jax.grad(theirs, argnums=(0, 1, 2, 3, 4))(m, weights, gate, up, down)
    assert worst_gap(a, b) < 1e-4


def planted_routing(tokens, first_choice, second_choice):
    """Two choices a token, the held experts' pairs counted out: the first
    choice of ``first_choice`` tokens is expert 0 and the second choice of
    ``second_choice`` tokens expert 1 (which tokens: a fixed shuffle each);
    every other choice falls on experts 2 to 7, held elsewhere."""
    k = jax.random.split(jax.random.key(9), 3)
    t = jnp.arange(tokens)
    chosen = jnp.stack([
        jnp.where(jax.random.permutation(k[0], tokens) < first_choice, 0, 2 + t % 3),
        jnp.where(jax.random.permutation(k[1], tokens) < second_choice, 1, 5 + t % 3),
    ], axis=1)
    weights = jax.random.uniform(k[2], (tokens, 2), minval=0.1)
    return weights / weights.sum(axis=1, keepdims=True), chosen


# 1,024 tokens, two choices each: 2,048 pairs. Two held of eight make chunks
# of 1,024 rows (two), one held of eight chunks of 512 (four)
CHUNKED = {
    "every held pair in one chunk": (2, 500, 300),
    "a few rows spill into the second chunk": (2, 1024, 6),
    "an expert's group lies across a chunk's edge": (2, 600, 900),
    "every chunk full": (2, 1024, 1024),
    "no held pair": (2, 0, 0),
    "one held expert's group over two chunks of four": (1, 900, 0),
}


@pytest.mark.parametrize("case", list(CHUNKED))
def test_as_many_chunks_run_as_hold_a_held_pair_and_nothing_changes(case):
    """Result, gradients and the counter where the held pairs fill one chunk,
    lie across an edge, fill every chunk or do not exist: against the dense
    sum, which knows no chunk. With no held pair the loop makes no turn and
    the count is 0."""
    held, first_choice, second_choice = CHUNKED[case]
    tokens = 1024
    m, _, _, gate, up, down = expert_inputs(tokens)
    weights, chosen = planted_routing(tokens, first_choice, second_choice)
    gate, up, down = gate[:held], up[:held], down[:held]
    chunk = experts.chunk_rows(2 * tokens, held, TINY.experts)
    assert chunk == {2: 1024, 1: 512}[held]

    def ours(m, weights, gate, up, down):
        y, load, chunks = experts.held_experts(
            m, weights, chosen, gate, up, down, 0, TINY.experts, jnp.float32)
        return jnp.sum(jnp.sin(y)), (y, load, chunks)

    def theirs(m, weights, gate, up, down):
        y = ref.held_share(m, weights, chosen, gate, up, down, 0, None)
        return jnp.sum(jnp.sin(y)), y

    with jax.default_matmul_precision("highest"):
        (_, (y, load, chunks)), a = jax.value_and_grad(
            ours, argnums=(0, 1, 2, 3, 4), has_aux=True)(m, weights, gate, up, down)
        (_, dense), b = jax.value_and_grad(
            theirs, argnums=(0, 1, 2, 3, 4), has_aux=True)(m, weights, gate, up, down)
    total = first_choice + (second_choice if held == 2 else 0)
    assert int(load.sum()) == total
    assert int(chunks) == -(-total // chunk)
    np.testing.assert_allclose(y, dense, rtol=1e-4, atol=1e-5)
    assert worst_gap(a, b) < 1e-4


@pytest.mark.parametrize("rows,held,of,chunk", [
    (98304, 16, 64, 32768),  # the cell: an even router's 24,576 and a third more
    (98304, 64, 64, 98304),  # every expert held: one chunk, every pair in it
    (98304, 8, 64, 16384),
    (2048, 2, 8, 1024),
    (192, 2, 8, 192),  # nothing smaller is a multiple of the row tile
    (256, 4, 8, 256),
])
def test_a_chunk_follows_from_shapes(rows, held, of, chunk):
    assert experts.chunk_rows(rows, held, of) == chunk
    assert rows % chunk == 0 and (chunk == rows or chunk % experts.ROW_TILE == 0)


class TestPacking:
    def test_rows_hold_a_permutation_of_the_lengths_and_nothing_else(self, views):
        docs, keys, (v0, v1) = views
        for view in (v0, v1):
            assert view.tokens.shape == view.segments.shape == view.positions.shape == (2, 64)
            for r in range(2):
                lengths = np.asarray(view.lengths[r])
                assert sorted(lengths.tolist()) == sorted(CROPS) and lengths.sum() == 64
                segments, positions = np.asarray(view.segments[r]), np.asarray(view.positions[r])
                assert segments.tolist() == np.repeat(np.arange(8), lengths).tolist()
                assert positions.tolist() == np.concatenate([np.arange(n) for n in lengths]).tolist()
        assert not np.array_equal(v0.lengths, v1.lengths)  # each view its own draw

    def test_every_crop_is_a_span_of_its_own_document(self, views):
        docs, keys, (v0, _) = views
        for r in range(2):
            at = 0
            for s, n in enumerate(np.asarray(v0.lengths[r])):
                crop, doc = np.asarray(v0.tokens[r, at:at + n]), docs.images[r * 8 + s]
                assert any(np.array_equal(crop, doc[o:o + n]) for o in range(128 - n + 1))
                at += n

    def test_one_documents_tokens_move_no_other_documents_output(self, views):
        docs, keys, (v0, _) = views
        model, params, stats = model_and_variables(4, 0, v0)
        encode = jax.jit(lambda view: model.apply(
            {"params": params, "batch_stats": stats}, view, train=True, method=model.encode))
        before = encode(v0)
        changed = np.array(docs.images)
        changed[3] = (changed[3] + 7) % TINY.vocab_held
        w0, _ = span_crop_views(keys, jnp.asarray(changed), CROPS)
        after = encode(w0)
        others = [d for d in range(16) if d != 3]
        assert np.array_equal(np.asarray(before)[others], np.asarray(after)[others])  # the same bits
        assert not np.array_equal(np.asarray(before)[3], np.asarray(after)[3])


def test_pooling_is_the_mean_over_each_documents_tokens(views):
    _, _, (v0, _) = views
    h = jax.random.normal(jax.random.key(2), (2, 64, 5))
    pooled = np.asarray(pool_segments(h, v0.segments, v0.lengths))
    for r in range(2):
        for s in range(8):
            mine = np.asarray(h[r])[np.asarray(v0.segments[r]) == s].mean(axis=0)
            np.testing.assert_allclose(pooled[r * 8 + s], mine, rtol=1e-5, atol=1e-6)


def test_the_encoder_counts_what_its_held_experts_took(views):
    _, _, (v0, _) = views
    spec = dataclasses.replace(TINY, experts_held=8)
    encoder = SequenceEncoder(spec=spec, dtype=jnp.float32)
    variables = encoder.init(jax.random.key(0), v0)
    _, mut = encoder.apply({"params": variables["params"]}, v0, mutable=["counters"])
    load = mut["counters"]["expert_load"]  # one leaf: (layers, held experts)
    assert load.shape == (len(spec.layout), spec.experts_held)
    # all held: every pair of every layer
    assert load.sum(axis=1).tolist() == [2 * 64 * spec.experts_per_token] * len(spec.layout)
    # and with them one chunk of all rows, which every layer's one call ran
    assert mut["counters"]["expert_chunks"].tolist() == [1] * len(spec.layout)
    assert mut["counters"]["expert_calls"].tolist() == [1] * len(spec.layout)
