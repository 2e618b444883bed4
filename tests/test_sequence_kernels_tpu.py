"""The sequence encoder's two kernel paths compile for a v5e at the cell's
real widths: splash attention (full causal, and the 4,096 window) over rows
of 8,192 tokens with 28 query on 4 key-value heads of 128, and the grouped
expert products (megablox) for 16 held experts of 2560-768-2560 over a
chunk of 32,768 of a view's 98,304 sorted (token, expert) pairs, inside the
loop over chunks, each forward and backward. Compiled
ahead of time by the TPU compiler installed here, for a chip that is
described and not attached: what Mosaic refuses (a tile off the tiling, too
much fast memory) shows here and costs no chip time. That proves
compilation, not numerics (``tests/test_sequence_encoder.py`` has those, on
the plain paths the CPU takes).

All in this one file and behind a fixture, so that one test worker loads
the TPU's library, after collection (the on-chip-measurement guide, 2)."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from simclr_tpu.models import attention, experts
from simclr_tpu.models.arch import SEQUENCE_ENCODERS

SPEC = SEQUENCE_ENCODERS["smallthinker-21ba3b-enc"]
ROW = 8192


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def kernels(monkeypatch):
    """The choice the program makes on a TPU, made here for the compile."""
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(experts, "on_tpu", lambda: True)
    attention._kernel.cache_clear()
    # a cache written for an absent chip cannot be read back: keep it silent
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    attention._kernel.cache_clear()


def compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("window", [None, SPEC.window])
def test_attention_forward_and_backward_compile_as_kernels(one_chip, kernels, window):
    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, segments):
        return jnp.sum(attention.attention(q, k, v, segments, window).astype(jnp.float32))

    text = compiled_text(
        jax.grad(loss, argnums=(0, 1, 2)),
        sds(1, ROW, SPEC.heads, SPEC.head_dim), sds(1, ROW, SPEC.kv_heads, SPEC.head_dim),
        sds(1, ROW, SPEC.kv_heads, SPEC.head_dim), sds(1, ROW, dtype=jnp.int32),
    )
    # forward, dq and dkv for each of the 4 key-value heads, or batched
    assert text.count("tpu_custom_call") >= 3


def test_the_held_experts_products_compile_as_kernels(one_chip, kernels):
    tokens = 2 * ROW  # one view
    pairs = tokens * SPEC.experts_per_token
    chunk = experts.chunk_rows(pairs, SPEC.experts_held, SPEC.experts)
    assert (pairs, chunk) == (98304, 32768)

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    @jax.checkpoint  # as the encoder runs a layer (models/sequence.py)
    def layer(m, weights, chosen, gate, up, down):
        with jax.named_scope("experts"):
            return experts.held_experts(
                m, weights, chosen, gate, up, down, 0, SPEC.experts, jnp.bfloat16)[0]

    def loss(*args):
        return jnp.sum(jnp.sin(layer(*args)))

    text = compiled_text(
        jax.grad(loss, argnums=(0, 1, 3, 4, 5)),
        sds(tokens, SPEC.hidden), sds(tokens, SPEC.experts_per_token),
        sds(tokens, SPEC.experts_per_token, dtype=jnp.int32),
        sds(SPEC.experts_held, SPEC.hidden, SPEC.expert_width),
        sds(SPEC.experts_held, SPEC.hidden, SPEC.expert_width),
        sds(SPEC.experts_held, SPEC.expert_width, SPEC.hidden),
    )
    # three products forward; each gives two backward (its rows, its weights),
    # and the backward pass runs the three again in place of the rematerialised
    # forward, whose own are dead code: twelve, as with a row for every pair
    # (3 forward + 3 rematerialised + 6 backward), whatever the chunks' number
    assert 9 <= text.count("tpu_custom_call") <= 12
    # nothing the chip writes has a row for every pair: the widest results are
    # a chunk's rows and the tokens' own. Neither token-side array of the
    # parent's is admitted: the return to the tokens is gathers of
    # ``tokens`` rows from a chunk, added in float32
    assert f"[{chunk},{SPEC.hidden}]" in text and f"[{tokens},{SPEC.hidden}]" in text
    for shape in (
        f"[{pairs},{SPEC.hidden}]", f"[{SPEC.hidden},{pairs}]", f"[{pairs},{SPEC.expert_width}]",
        f"[{tokens},{SPEC.experts_per_token},{SPEC.hidden}]",
    ):
        assert shape not in text, shape
