"""Compressed gradient all-reduce (parallel/compress.py).

Three layers of evidence on the 8-device CPU mesh (conftest):

  * quantizer math — stochastic-rounding unbiasedness, bucket-boundary
    shapes, pytree round-trip structure/dtype preservation, and an
    elementwise worst-case error bound derived from the per-bucket scales;
  * drop-in equivalence — ``grad_allreduce`` against ``jax.lax.psum`` of the
    same pytree inside ``shard_map``, for every mode;
  * train-path equivalence — the dp, supervised and dp x tp steps each
    trained a few steps under ``bf16``/``int8`` land within tolerance of
    their ``exact`` trajectory.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from simclr_tpu.ops.lars import lars, simclr_weight_decay_mask
from simclr_tpu.parallel import compress
from simclr_tpu.parallel.compress import (
    DEFAULT_BUCKET_SIZE,
    GRAD_ALLREDUCE_MODES,
    WEIGHT_QUANT_MODES,
    allreduce_wire_bytes,
    dequantize_weight_buckets,
    grad_allreduce,
    quantize_weight_buckets,
    validate_weight_mode,
    weight_storage_bytes,
)
from simclr_tpu.parallel.mesh import (
    DATA_AXIS,
    MeshSpec,
    batch_sharding,
    create_mesh,
)
from simclr_tpu.parallel.steps import make_pretrain_step, make_supervised_step
from simclr_tpu.parallel.train_state import create_train_state
from tests.helpers import TinyContrastive, TinySupervised, random_images

N_DEV = 8


def _allreduce_on_mesh(tree, mode, *, bucket_size=DEFAULT_BUCKET_SIZE, seed=0,
                       overlap="off", chunks=1):
    """Run ``grad_allreduce`` under shard_map: device i contributes
    ``tree + i * 0.01`` per leaf; returns (per-device stacked result, the
    exact psum). Keys are folded per data shard, as the train steps do."""
    mesh = create_mesh()
    tree = jax.tree.map(jnp.asarray, tree)

    def f(_, tree):
        i = jax.lax.axis_index(DATA_AXIS)
        local = jax.tree.map(lambda l: l + 0.01 * i.astype(l.dtype), tree)
        key = jax.random.fold_in(jax.random.key(seed), i)
        out = grad_allreduce(
            local, DATA_AXIS, mode, key=key, bucket_size=bucket_size,
            overlap=overlap, chunks=chunks,
        )
        exact = jax.lax.psum(local, DATA_AXIS)
        return jax.tree.map(lambda x: x[None], (out, exact))

    # the tree is an explicit replicated argument under jit: an eager
    # shard_map closing over committed arrays trips jax's "Unexpected XLA
    # sharding override" check
    got, exact = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P(DATA_AXIS), P()), out_specs=P(DATA_AXIS),
        check_vma=False,
    ))(jnp.zeros((N_DEV,)), tree)
    return jax.device_get(got), jax.device_get(exact)


# ---------------------------------------------------------------------------
# Quantizer math
# ---------------------------------------------------------------------------

class TestQuantizer:
    def test_stochastic_rounding_unbiased(self):
        """mean over many keys of dequant(quant(x)) -> x (the estimator is
        unbiased), with the error shrinking as 1/sqrt(n_keys)."""
        x = jax.random.normal(jax.random.key(3), (4, 64), jnp.float32)
        n_keys = 4000

        def once(key):
            q, scale = compress._quantize(x, key)
            return q.astype(jnp.float32) * scale[:, None]

        deq = jax.vmap(once)(jax.random.split(jax.random.key(0), n_keys))
        mean = np.asarray(jnp.mean(deq, axis=0))
        quantum = np.asarray(jnp.max(jnp.abs(x), axis=1) / 127.0)[:, None]
        # SR error is uniform in (-quantum, quantum): the mean of n_keys draws
        # has sd <= quantum/sqrt(3 n_keys); 6 sigma never flakes
        bound = 6.0 * quantum / np.sqrt(3.0 * n_keys)
        assert np.all(np.abs(mean - np.asarray(x)) < bound)

    def test_single_rounding_within_one_quantum(self):
        x = jax.random.normal(jax.random.key(1), (8, 32), jnp.float32) * 5.0
        q, scale = compress._quantize(x, jax.random.key(2))
        deq = np.asarray(q.astype(jnp.float32) * scale[:, None])
        quantum = np.asarray(scale)[:, None]
        assert np.all(np.abs(deq - np.asarray(x)) <= quantum + 1e-7)

    def test_zero_bucket_stays_zero(self):
        x = jnp.zeros((2, 16), jnp.float32)
        q, scale = compress._quantize(x, jax.random.key(0))
        assert np.all(np.asarray(q) == 0)
        assert np.all(np.asarray(scale) == 0.0)


# ---------------------------------------------------------------------------
# Mode surface + wire accounting
# ---------------------------------------------------------------------------

class TestModes:
    def test_unknown_mode_rejected_with_valid_set(self):
        with pytest.raises(ValueError, match="exact.*bf16.*int8"):
            grad_allreduce({"w": jnp.ones(3)}, DATA_AXIS, "fp4")

    def test_int8_requires_key(self):
        with pytest.raises(ValueError, match="requires a PRNG key"):
            grad_allreduce({"w": jnp.ones(3)}, DATA_AXIS, "int8")

    def test_empty_pytree_passthrough(self):
        assert grad_allreduce({}, DATA_AXIS, "int8", key=jax.random.key(0)) == {}

    def test_wire_bytes_table(self):
        n = 11_172_032  # ~resnet18+head gradient elements
        exact = allreduce_wire_bytes(n, 8, "exact")
        bf16 = allreduce_wire_bytes(n, 8, "bf16")
        int8 = allreduce_wire_bytes(n, 8, "int8")
        assert exact == pytest.approx(2 * 7 / 8 * 4 * n)
        assert bf16 == pytest.approx(exact / 2)
        # the acceptance headline: >= 3x reduction at ResNet-18 size
        assert exact / int8 >= 3.0
        with pytest.raises(ValueError):
            allreduce_wire_bytes(n, 8, "fp4")

    def test_wire_bytes_counts_bucket_padding(self):
        # 1 element still ships one full padded bucket per phase
        got = allreduce_wire_bytes(1, 8, "int8", bucket_size=256)
        assert got == pytest.approx(2 * 7 / 8 * (8 * 256 + 4 * 8))


# ---------------------------------------------------------------------------
# Drop-in equivalence vs psum on the mesh (all modes, awkward shapes)
# ---------------------------------------------------------------------------

class TestAllreduceEquivalence:
    TREE = {
        "single": np.float32([0.37]),                      # one element
        "empty": np.zeros((0, 3), np.float32),             # empty tail leaf
        "odd": np.linspace(-2, 2, 97, dtype=np.float32),   # non-multiple of bucket
        "block": np.linspace(-1, 1, 256, dtype=np.float32).reshape(16, 16),
    }

    def test_exact_is_psum(self):
        got, exact = _allreduce_on_mesh(self.TREE, "exact", bucket_size=32)
        jax.tree.map(np.testing.assert_array_equal, got, exact)

    def test_bf16_within_bf16_eps(self):
        got, exact = _allreduce_on_mesh(self.TREE, "bf16", bucket_size=32)
        # one cast per contribution + one on the sum: a few bf16 ulps
        jax.tree.map(
            lambda g, e: np.testing.assert_allclose(
                g, e, rtol=2.0 ** -6, atol=2.0 ** -6
            ),
            got, exact,
        )

    def test_int8_within_quantum_bound(self):
        """Elementwise worst-case bound: each of the 8 contributions rounds
        by < its bucket quantum, plus one requantization of the sum."""
        got, exact = _allreduce_on_mesh(self.TREE, "int8", bucket_size=32)
        flat_exact = np.concatenate(
            [np.asarray(l[0]).ravel() for l in jax.tree.leaves(exact)]
        )
        # conservative global bound on the per-bucket quanta
        local_amax = max(
            float(np.max(np.abs(np.asarray(l)), initial=0.0))
            for l in self.TREE.values()
        ) + 0.01 * (N_DEV - 1)
        # 8 contributions round by < one local quantum each; the requantized
        # sum's amax can exceed exact's by that accumulated error (1.1 slack)
        bound = 1.1 * (N_DEV * local_amax + float(np.max(np.abs(flat_exact)))) / 127.0
        err = jax.tree.map(
            lambda g, e: np.max(np.abs(g - e), initial=0.0), got, exact
        )
        assert max(jax.tree.leaves(err)) <= bound

    @pytest.mark.parametrize("mode", ["bf16", "int8"])
    def test_replica_identical_and_structure_round_trip(self, mode):
        got, _ = _allreduce_on_mesh(self.TREE, mode, bucket_size=32)
        assert jax.tree.structure(got) == jax.tree.structure(
            jax.tree.map(jnp.asarray, self.TREE)
        )
        for name, leaf in got.items():
            leaf = np.asarray(leaf)
            assert leaf.shape[1:] == self.TREE[name].shape
            assert leaf.dtype == self.TREE[name].dtype
            for j in range(1, N_DEV):  # all replicas bitwise identical
                np.testing.assert_array_equal(leaf[0], leaf[j], err_msg=name)

    def test_int8_reproducible_and_key_sensitive(self):
        a, _ = _allreduce_on_mesh(self.TREE, "int8", bucket_size=32, seed=5)
        b, _ = _allreduce_on_mesh(self.TREE, "int8", bucket_size=32, seed=5)
        c, _ = _allreduce_on_mesh(self.TREE, "int8", bucket_size=32, seed=6)
        jax.tree.map(np.testing.assert_array_equal, a, b)
        assert any(
            not np.array_equal(x, y)
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(c))
        )

    def test_bucket_exactly_divides(self):
        tree = {"w": np.linspace(-1, 1, 8 * 32, dtype=np.float32)}
        got, exact = _allreduce_on_mesh(tree, "int8", bucket_size=32)
        assert np.max(np.abs(got["w"] - exact["w"])) < 0.05 * np.max(np.abs(exact["w"])) + 0.05


# ---------------------------------------------------------------------------
# Chunked ring (comm_overlap=chunked): parity vs single-shot, invariants
# ---------------------------------------------------------------------------

_CHUNKED_CACHE: dict = {}


def _chunked_on_mesh(mode, chunks, seed=0):
    """Memoized chunked-ring run: an unrolled int8 ring costs ~35 s of XLA
    compile on the CPU mesh, so the invariant tests share one execution."""
    k = (mode, chunks, seed)
    if k not in _CHUNKED_CACHE:
        _CHUNKED_CACHE[k] = _allreduce_on_mesh(
            TestAllreduceEquivalence.TREE, mode, bucket_size=32, seed=seed,
            overlap="chunked", chunks=chunks,
        )
    return _CHUNKED_CACHE[k]


class TestChunkedRing:
    TREE = TestAllreduceEquivalence.TREE

    # int8 rings requantize the running partial at every reduce-scatter hop
    # (n-1 extra roundings vs single-shot), so the bound is hop-scaled; bf16
    # accumulates pairwise in bf16 over n-1 hops
    RING_TOL = {"exact": 1e-5, "bf16": 2.0 ** -4, "int8": None}

    # chunks=3 does not divide the 97/256/354-element layout: every mode
    # crosses a ragged tail chunk; chunks=1 pins the single-ring degenerate.
    # Only the exact rings ride the fast tier: each compressed-mode mesh
    # program costs 20-50 s of CPU XLA compile and the 870 s tier-1 budget
    # is full — bf16/int8 chunked rings keep fast-tier behavioral coverage
    # through TestTrainPathChunked and ride here in the slow tier.
    @pytest.mark.parametrize("mode,chunks", [
        ("exact", 1), ("exact", 3),
        pytest.param("bf16", 3, marks=pytest.mark.slow),
        pytest.param("int8", 3, marks=pytest.mark.slow),
        pytest.param("bf16", 8, marks=pytest.mark.slow),
        pytest.param("int8", 8, marks=pytest.mark.slow),
    ])
    def test_chunked_matches_psum_within_mode_tolerance(self, mode, chunks):
        got, exact = _chunked_on_mesh(mode, chunks)
        if mode == "int8":
            flat_exact = np.concatenate(
                [np.asarray(l[0]).ravel() for l in jax.tree.leaves(exact)]
            )
            local_amax = max(
                float(np.max(np.abs(np.asarray(l)), initial=0.0))
                for l in self.TREE.values()
            ) + 0.01 * (N_DEV - 1)
            # each of n-1 hops rounds the running partial (amax <= n*local)
            # by one quantum, plus the gather-phase requantization
            bound = 1.1 * N_DEV * (
                N_DEV * local_amax + float(np.max(np.abs(flat_exact)))
            ) / 127.0
            err = jax.tree.map(
                lambda g, e: np.max(np.abs(g - e), initial=0.0), got, exact
            )
            assert max(jax.tree.leaves(err)) <= bound
        else:
            tol = self.RING_TOL[mode]
            jax.tree.map(
                lambda g, e: np.testing.assert_allclose(g, e, rtol=tol, atol=tol),
                got, exact,
            )

    # slow: shares the _chunked_on_mesh(mode, 3) runs with the psum
    # tolerance params above — keeping it fast would recompile them
    @pytest.mark.slow
    @pytest.mark.parametrize("mode", ["bf16", "int8"])
    def test_chunked_replicas_bitwise_identical(self, mode):
        """The gather phase forwards each owner's wire bytes VERBATIM, so
        every replica dequantizes identical payloads — the invariant the
        jit-level LARS update relies on survives chunking."""
        got, _ = _chunked_on_mesh(mode, 3)
        for name, leaf in got.items():
            leaf = np.asarray(leaf)
            for j in range(1, N_DEV):
                np.testing.assert_array_equal(leaf[0], leaf[j], err_msg=name)

    def test_off_bitwise_identical_to_default_call(self):
        """overlap="off" IS the pre-knob single-shot path: bitwise-equal
        output to a call that never mentions overlap, for the stochastic
        mode where any code motion would show."""
        a, _ = _allreduce_on_mesh(self.TREE, "int8", bucket_size=32, seed=4)
        b, _ = _allreduce_on_mesh(
            self.TREE, "int8", bucket_size=32, seed=4, overlap="off", chunks=7
        )
        jax.tree.map(np.testing.assert_array_equal, a, b)

    @pytest.mark.slow
    def test_chunks_exceeding_elements(self):
        """More chunks than elements degrades to one ring per element —
        never an empty chunk, result still the psum."""
        tree = {"w": np.linspace(-1, 1, 5, dtype=np.float32)}
        got, exact = _allreduce_on_mesh(
            tree, "exact", overlap="chunked", chunks=64
        )
        np.testing.assert_allclose(got["w"], exact["w"], rtol=1e-5, atol=1e-6)

    @pytest.mark.slow
    def test_chunked_reproducible_and_chunk_count_sensitive(self):
        """Per-chunk keys: same (seed, chunks) reproduces bitwise; a
        different chunk count re-keys the quantizer and must not reproduce
        (a silent key-reuse bug would)."""
        a, _ = _allreduce_on_mesh(
            self.TREE, "int8", bucket_size=32, seed=5, overlap="chunked", chunks=3
        )
        b, _ = _allreduce_on_mesh(
            self.TREE, "int8", bucket_size=32, seed=5, overlap="chunked", chunks=3
        )
        c, _ = _allreduce_on_mesh(
            self.TREE, "int8", bucket_size=32, seed=5, overlap="chunked", chunks=2
        )
        jax.tree.map(np.testing.assert_array_equal, a, b)
        assert any(
            not np.array_equal(x, y)
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(c))
        )

    def test_overlap_validation(self):
        with pytest.raises(ValueError, match="off.*chunked"):
            compress.validate_overlap("ring")
        for bad in (0, -1, compress.MAX_COMM_CHUNKS + 1, 2.5):
            with pytest.raises(ValueError, match=r"\[1, 64\]"):
                compress.validate_overlap("chunked", bad)
        compress.validate_overlap("chunked", compress.MAX_COMM_CHUNKS)
        with pytest.raises(ValueError, match="comm_overlap"):
            grad_allreduce(
                {"w": jnp.ones(3)}, DATA_AXIS, "exact", overlap="ring"
            )

    def test_normalize_overlap_yaml_false(self):
        # YAML 1.1 parses bare `off` as boolean False; the config boundary
        # must land on the string before validation
        assert compress.normalize_overlap(False) == "off"
        assert compress.normalize_overlap("chunked") == "chunked"

    def test_async_wire_bytes_match_chunked(self):
        # async issues the SAME per-chunk rings as chunked, just eagerly —
        # the analytic wire accounting is identical by construction
        n = 2**20
        for mode in GRAD_ALLREDUCE_MODES:
            assert allreduce_wire_bytes(
                n, 8, mode, overlap="async", chunks=8
            ) == allreduce_wire_bytes(n, 8, mode, overlap="chunked", chunks=8)

    def test_chunked_wire_bytes(self):
        n = 8 * 1024
        # exact fp32: chunking contiguous fp32 segments adds no padding
        # when every chunk stays a multiple of the axis size
        assert allreduce_wire_bytes(
            n, 8, "exact", overlap="chunked", chunks=4
        ) == pytest.approx(allreduce_wire_bytes(n, 8, "exact"))
        # int8: per-chunk bucket padding can only add bytes, and stays
        # small relative to the payload at real sizes
        off = allreduce_wire_bytes(2**20, 8, "int8")
        on = allreduce_wire_bytes(2**20, 8, "int8", overlap="chunked", chunks=8)
        assert off <= on <= 1.1 * off
        with pytest.raises(ValueError, match="comm_chunks"):
            allreduce_wire_bytes(n, 8, "exact", overlap="chunked", chunks=0)


# ---------------------------------------------------------------------------
# Async eager rings (comm_overlap=async): bitwise-equal gradient to chunked
# ---------------------------------------------------------------------------

_ASYNC_CACHE: dict = {}


def _async_on_mesh(mode, chunks, seed=0):
    """Memoized async-ring run (same economics as _chunked_on_mesh)."""
    k = (mode, chunks, seed)
    if k not in _ASYNC_CACHE:
        _ASYNC_CACHE[k] = _allreduce_on_mesh(
            TestAllreduceEquivalence.TREE, mode, bucket_size=32, seed=seed,
            overlap="async", chunks=chunks,
        )
    return _ASYNC_CACHE[k]


class TestAsyncRing:
    TREE = TestAllreduceEquivalence.TREE

    # chunks=3 crosses ragged chunk AND leaf boundaries (97/256/354-element
    # layout): buckets are assembled from partial leaf slices and scattered
    # back across leaves; chunks=1 pins the single-bucket degenerate
    # the CPU mesh pays ~30-110 s of XLA compile per unrolled ring
    # program, and the 870 s tier-1 budget is nearly full: the fast tier
    # carries only the single-bucket degenerate; the ragged multi-leaf
    # cases across all three modes plus the chunks=8 sweep ride in the
    # slow tier (all verified on the 8-device mesh)
    @pytest.mark.parametrize("mode,chunks", [
        pytest.param("exact", 1, marks=pytest.mark.slow),
        pytest.param("exact", 3, marks=pytest.mark.slow),
        pytest.param("bf16", 3, marks=pytest.mark.slow),
        pytest.param("int8", 3, marks=pytest.mark.slow),
        pytest.param("int8", 8, marks=pytest.mark.slow),
    ])
    def test_async_bitwise_equals_chunked(self, mode, chunks):
        """The tentpole invariant: for the same bucket assignment, async
        hands LARS the SAME dequantized gradient as the chunked ring —
        bitwise, including stochastic int8. The eager path reuses
        _chunk_bounds over the same leaf-order flat layout, the same
        fold_in(key, c) per-bucket keys, and the same _ring_chunk_allreduce;
        only the issue order (reverse-topological) and the bucket
        gather/scatter differ, neither of which touches a value."""
        got, _ = _async_on_mesh(mode, chunks)
        want, _ = _chunked_on_mesh(mode, chunks)
        jax.tree.map(np.testing.assert_array_equal, got, want)

    @pytest.mark.slow
    def test_async_replicas_bitwise_identical(self):
        """The verbatim-forwarding gather survives eager issue: every
        replica dequantizes identical int8 payloads, so the jit-level LARS
        update keeps replicas in lockstep under async too."""
        got, _ = _async_on_mesh("int8", 3)
        for name, leaf in got.items():
            leaf = np.asarray(leaf)
            for j in range(1, N_DEV):
                np.testing.assert_array_equal(leaf[0], leaf[j], err_msg=name)

    @pytest.mark.slow
    def test_async_exact_matches_psum(self):
        got, exact = _async_on_mesh("exact", 3)
        jax.tree.map(
            lambda g, e: np.testing.assert_allclose(g, e, rtol=1e-5, atol=1e-5),
            got, exact,
        )

    @pytest.mark.slow
    def test_async_chunks_exceeding_elements(self):
        """More buckets than elements degrades like chunked: one ring per
        element, never an empty bucket, result still the psum."""
        tree = {"w": np.linspace(-1, 1, 5, dtype=np.float32)}
        got, exact = _allreduce_on_mesh(
            tree, "exact", overlap="async", chunks=64
        )
        np.testing.assert_allclose(got["w"], exact["w"], rtol=1e-5, atol=1e-6)

    def test_async_validation(self):
        assert compress.COMM_OVERLAP_MODES == ("off", "chunked", "async")
        compress.validate_overlap("async", compress.MAX_COMM_CHUNKS)
        for bad in (0, -1, compress.MAX_COMM_CHUNKS + 1, 2.5):
            with pytest.raises(ValueError, match=r"\[1, 64\]"):
                compress.validate_overlap("async", bad)
        with pytest.raises(ValueError, match="comm_chunks"):
            grad_allreduce(
                {"w": jnp.ones(3)}, DATA_AXIS, "exact", overlap="async",
                chunks=0,
            )


# ---------------------------------------------------------------------------
# Train-path equivalence: dp step, supervised step
# ---------------------------------------------------------------------------

def _tx():
    return lars(0.1, weight_decay=1e-4, weight_decay_mask=simclr_weight_decay_mask)


def _pretrain_losses(mode, n_steps=2, batch=16, **step_kwargs):
    mesh = create_mesh()
    model = TinyContrastive(bn_cross_replica_axis=DATA_AXIS)
    tx = _tx()
    state = create_train_state(
        model, tx, jax.random.key(0), jnp.zeros((batch, 32, 32, 3), jnp.float32)
    )
    step = make_pretrain_step(
        model, tx, mesh, temperature=0.5, strength=0.5, negatives="global",
        grad_allreduce=mode, **step_kwargs,
    )
    sharding = batch_sharding(mesh)
    losses = []
    for i in range(n_steps):
        images = jax.device_put(random_images(batch, seed=i), sharding)
        state, metrics = step(state, images, jax.random.key(100 + i))
        losses.append(float(metrics["loss"]))
    return losses


def _supervised_losses(mode, n_steps=2, batch=16, **step_kwargs):
    mesh = create_mesh()
    model = TinySupervised(bn_cross_replica_axis=DATA_AXIS)
    tx = _tx()
    state = create_train_state(
        model, tx, jax.random.key(0), jnp.zeros((batch, 32, 32, 3), jnp.float32)
    )
    step = make_supervised_step(
        model, tx, mesh, strength=0.5, grad_allreduce=mode, **step_kwargs
    )
    sharding = batch_sharding(mesh)
    labels = jax.device_put(
        jnp.asarray(np.arange(batch, dtype=np.int32) % 10), sharding
    )
    losses = []
    for i in range(n_steps):
        images = jax.device_put(random_images(batch, seed=i), sharding)
        state, metrics = step(state, images, labels, jax.random.key(100 + i))
        losses.append(float(metrics["loss"]))
    return losses


# quantized updates perturb the trajectory from step 2 on; LARS normalizes
# away the gradient scale so the loss drift stays small. bf16 rounds
# deterministically (tighter), int8 adds one-quantum-per-bucket noise.
TOL = {"bf16": 2e-2, "int8": 5e-2}

# trajectory runs are deterministic, and several classes compare against
# the same baselines (the exact dp/supervised losses) — share one
# execution per signature, same economics as _CHUNKED_CACHE
_TRAJ_CACHE: dict = {}


def _cached(fn, mode, **kw):
    k = (fn.__name__, mode, tuple(sorted(kw.items())))
    if k not in _TRAJ_CACHE:
        _TRAJ_CACHE[k] = fn(mode, **kw)
    return _TRAJ_CACHE[k]


@pytest.mark.parametrize("mode", ["bf16", "int8"])
class TestTrainPathEquivalence:
    def test_dp_per_step(self, mode):
        exact = _cached(_pretrain_losses, "exact")
        got = _cached(_pretrain_losses, mode)
        assert all(np.isfinite(got))
        np.testing.assert_allclose(got, exact, atol=TOL[mode])

    def test_supervised(self, mode):
        exact = _cached(_supervised_losses, "exact")
        got = _cached(_supervised_losses, mode)
        assert all(np.isfinite(got))
        np.testing.assert_allclose(got, exact, atol=TOL[mode])


# ---------------------------------------------------------------------------
# dp x tp: compress over data only; model replicas must stay in lockstep
# ---------------------------------------------------------------------------

def _tp_losses(mode, n_steps=2, per_device_batch=2, **step_kwargs):
    from simclr_tpu.models.contrastive import ContrastiveModel
    from simclr_tpu.parallel.tp import make_pretrain_step_tp, tp_state_shardings
    from simclr_tpu.utils.schedule import warmup_cosine_schedule

    mesh = create_mesh(MeshSpec(data=4, model=2))
    model = ContrastiveModel(
        base_cnn="resnet18", d=128, dtype=jnp.float32,
        bn_cross_replica_axis=DATA_AXIS,
    )
    tx = lars(
        warmup_cosine_schedule(0.1, 20, 2),
        weight_decay=1e-4,
        weight_decay_mask=simclr_weight_decay_mask,
    )
    state = create_train_state(
        model, tx, jax.random.key(7), jnp.zeros((2, 32, 32, 3), jnp.float32)
    )
    state = jax.device_put(state, tp_state_shardings(mesh, state))
    step = make_pretrain_step_tp(
        model, tx, mesh, temperature=0.5, strength=0.5, grad_allreduce=mode,
        **step_kwargs,
    )
    batch = jax.device_put(
        random_images(per_device_batch * 4, seed=0), batch_sharding(mesh)
    )
    losses = []
    for i in range(n_steps):
        state, metrics = step(state, batch, jax.random.key(100 + i))
        losses.append(float(metrics["loss"]))
    return losses, jax.device_get(state.params)


# ---------------------------------------------------------------------------
# Train-path: comm_overlap=chunked within dryrun parity tolerance of off
# ---------------------------------------------------------------------------

# chunked exact is the same fp32 sum in a different association order —
# loss-level drift is roundoff only; quantized modes inherit the step TOL
CHUNK_TOL = {"exact": 1e-4, "bf16": 2e-2, "int8": 5e-2}


class TestTrainPathChunked:
    @pytest.mark.parametrize("mode", ["exact", "int8"])
    def test_dp_per_step(self, mode):
        off = _cached(_pretrain_losses, mode)
        got = _cached(
            _pretrain_losses, mode, comm_overlap="chunked", comm_chunks=3
        )
        assert all(np.isfinite(got))
        np.testing.assert_allclose(got, off, atol=CHUNK_TOL[mode])


# ---------------------------------------------------------------------------
# Train-path: comm_overlap=async under the staged backward (jax.vjp chain)
# ---------------------------------------------------------------------------

class TestTrainPathAsync:
    """async restructures the step's backward (staged VJP + eager rings),
    so parity must be re-proven at the trajectory level, not just on the
    raw collective: the loss sequence under async must track off within
    the chunked tolerance, and under stochastic int8 it must track CHUNKED
    to roundoff — a key-schedule or bucket-boundary drift between the two
    paths would diverge at the ~1e-1 quantization-noise scale instead."""

    @pytest.mark.slow
    def test_dp_per_step_exact(self):
        off = _cached(_pretrain_losses, "exact")
        got = _pretrain_losses("exact", comm_overlap="async", comm_chunks=3)
        assert all(np.isfinite(got))
        np.testing.assert_allclose(got, off, atol=CHUNK_TOL["exact"])

    @pytest.mark.slow
    def test_dp_per_step_int8_tracks_chunked_key_schedule(self):
        chunked = _cached(
            _pretrain_losses, "int8", comm_overlap="chunked", comm_chunks=3
        )
        got = _pretrain_losses("int8", comm_overlap="async", comm_chunks=3)
        assert all(np.isfinite(got))
        np.testing.assert_allclose(got, chunked, rtol=1e-5, atol=1e-6)

    @pytest.mark.slow
    def test_supervised(self):
        """The supervised step's staged VJP carries a 3-tuple aux
        (stats, correct, n_local) — the async branch must thread it."""
        off = _cached(_supervised_losses, "exact")
        got = _supervised_losses("exact", comm_overlap="async", comm_chunks=3)
        assert all(np.isfinite(got))
        np.testing.assert_allclose(got, off, atol=CHUNK_TOL["exact"])


@pytest.mark.slow
def test_tp_async_matches_chunked():
    """dp x tp with async on the data axis: the staged backward inside the
    tp step must hand the model-axis replicas the chunked gradient (keys
    still fold the DATA index only), keeping them in lockstep."""
    chunked, _ = _tp_losses("int8", comm_overlap="chunked", comm_chunks=3)
    got, params = _tp_losses("int8", comm_overlap="async", comm_chunks=3)
    assert all(np.isfinite(got))
    np.testing.assert_allclose(got, chunked, rtol=1e-5, atol=1e-6)
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        assert np.all(np.isfinite(np.asarray(leaf))), jax.tree_util.keystr(path)


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_tp_data_axis_compression_matches_exact(mode):
    exact, params_exact = _tp_losses("exact")
    got, params = _tp_losses(mode)
    assert all(np.isfinite(got))
    np.testing.assert_allclose(got, exact, atol=TOL[mode])
    # replicated (encoder) leaves must remain consistent: the jit-level LARS
    # update only preserves replication if dequantized grads are replica-
    # identical across the model axis (keys fold the DATA index only)
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        assert np.all(np.isfinite(np.asarray(leaf))), jax.tree_util.keystr(path)


@pytest.mark.slow
def test_tp_chunked_ring_matches_off():
    """dp x tp with the chunked ring on the data axis: model-axis replicas
    must still receive identical dequantized gradients (the ring's
    verbatim-forwarding gather preserves the lockstep invariant)."""
    off, _ = _tp_losses("int8")
    got, params = _tp_losses("int8", comm_overlap="chunked", comm_chunks=3)
    assert all(np.isfinite(got))
    np.testing.assert_allclose(got, off, atol=CHUNK_TOL["int8"])
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        assert np.all(np.isfinite(np.asarray(leaf))), jax.tree_util.keystr(path)


def test_modes_registry():
    assert GRAD_ALLREDUCE_MODES == ("exact", "bf16", "int8")


class TestWeightQuantizer:
    """The serve-tier weight storage path (quantize once at engine load,
    dequantize inside the jitted forward). Distinct from the gradient
    quantizer above: round-to-nearest, not stochastic — determinism is the
    bitwise-repeatability contract across loads and replicas."""

    def test_roundtrip_error_bounded_by_half_scale(self):
        rng = np.random.default_rng(0)
        flat = rng.normal(size=4096 + 100).astype(np.float32)  # ragged tail
        q, scales = quantize_weight_buckets(flat)
        assert q.dtype == np.int8 and q.shape == (5, DEFAULT_BUCKET_SIZE)
        assert scales.dtype == np.float32 and scales.shape == (5,)
        back = np.asarray(dequantize_weight_buckets(q, scales, flat.size))
        per_bucket_bound = np.repeat(scales / 2, DEFAULT_BUCKET_SIZE)[: flat.size]
        assert np.all(np.abs(back - flat) <= per_bucket_bound + 1e-7)

    def test_deterministic_same_bytes_every_call(self):
        flat = np.random.default_rng(1).normal(size=3000).astype(np.float32)
        q1, s1 = quantize_weight_buckets(flat)
        q2, s2 = quantize_weight_buckets(flat.copy())
        np.testing.assert_array_equal(q1, q2)
        np.testing.assert_array_equal(s1, s2)

    def test_zero_and_empty_buckets(self):
        q, s = quantize_weight_buckets(np.zeros((10,), np.float32))
        assert np.all(q == 0) and np.all(s == 0.0)
        back = np.asarray(dequantize_weight_buckets(q, s, 10))
        np.testing.assert_array_equal(back, np.zeros(10, np.float32))
        q, s = quantize_weight_buckets(np.zeros((0,), np.float32))
        assert q.shape == (1, DEFAULT_BUCKET_SIZE)

    def test_storage_bytes_analytic_model(self):
        n = 5000
        assert weight_storage_bytes(n, "exact") == 4 * n
        assert weight_storage_bytes(n, "bf16") == 2 * n
        n_buckets = -(-n // DEFAULT_BUCKET_SIZE)
        assert weight_storage_bytes(n, "int8") == (
            n_buckets * DEFAULT_BUCKET_SIZE + 4 * n_buckets
        )
        # the headline: int8 resident weights ~3.98x under fp32
        assert weight_storage_bytes(n, "exact") / weight_storage_bytes(n, "int8") > 3.8

    def test_validate_weight_mode(self):
        assert WEIGHT_QUANT_MODES == ("exact", "bf16", "int8")
        for mode in WEIGHT_QUANT_MODES:
            assert validate_weight_mode(mode) == mode
        with pytest.raises(ValueError, match="serve.weights"):
            validate_weight_mode("fp8")
