"""NT-Xent correctness: naive-reference equivalence, sharding equivalence.

The naive implementation below is written directly from the SimCLR paper's
Eq. 1 (per-anchor softmax over the 2N-1 other embeddings), independent of
both the reference code and the framework implementation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from simclr_tpu.ops import (
    ntxent_loss,
    ntxent_loss_local_negatives,
    ntxent_loss_sharded_rows,
)


def naive_ntxent(z0: np.ndarray, z1: np.ndarray, temperature: float) -> float:
    """Paper Eq. 1, O(N^2) loops, float64."""
    z = np.concatenate([z0, z1]).astype(np.float64)
    z = z / np.linalg.norm(z, axis=1, keepdims=True)
    n2 = z.shape[0]
    n = n2 // 2
    total = 0.0
    for i in range(n2):
        j = (i + n) % n2  # positive partner
        sims = z @ z[i] / temperature
        numer = np.exp(sims[j])
        denom = sum(np.exp(sims[k]) for k in range(n2) if k != i)
        total += -np.log(numer / denom)
    return total / n2


@pytest.fixture(scope="module")
def batch():
    rng = np.random.RandomState(0)
    z0 = rng.randn(16, 8).astype(np.float32)
    z1 = rng.randn(16, 8).astype(np.float32)
    return z0, z1


def test_matches_naive_reference(batch):
    z0, z1 = batch
    for temp in (0.1, 0.5, 1.0):
        expected = naive_ntxent(z0, z1, temp)
        got = float(ntxent_loss(jnp.asarray(z0), jnp.asarray(z1), temperature=temp))
        assert got == pytest.approx(expected, rel=1e-5), f"temp={temp}"


def test_reductions(batch):
    z0, z1 = batch
    per = ntxent_loss(jnp.asarray(z0), jnp.asarray(z1), reduction="none")
    assert per.shape == (32,)
    s = float(ntxent_loss(jnp.asarray(z0), jnp.asarray(z1), reduction="sum"))
    m = float(ntxent_loss(jnp.asarray(z0), jnp.asarray(z1), reduction="mean"))
    assert s == pytest.approx(float(per.sum()), rel=1e-6)
    assert m == pytest.approx(s / 32, rel=1e-6)
    with pytest.raises(ValueError):
        ntxent_loss(jnp.asarray(z0), jnp.asarray(z1), reduction="bogus")


def _data_mesh():
    return Mesh(np.array(jax.devices()), ("data",))


def test_sharded_rows_equals_full_batch(batch):
    """Global-negative loss computed via shard_map all_gather must equal the
    single-array full-batch loss — value AND gradient."""
    z0, z1 = map(jnp.asarray, batch)
    mesh = _data_mesh()

    def sharded(z0, z1):
        return ntxent_loss_sharded_rows(z0, z1, axis_name="data", temperature=0.5)

    sharded_fn = jax.shard_map(
        sharded, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P(),
        check_vma=False,
    )
    full = float(ntxent_loss(z0, z1, temperature=0.5))
    got = float(jax.jit(sharded_fn)(z0, z1))
    assert got == pytest.approx(full, rel=1e-5)

    g_full = jax.grad(lambda a, b: ntxent_loss(a, b, temperature=0.5))(z0, z1)
    g_shard = jax.jit(jax.grad(lambda a, b: sharded_fn(a, b)))(z0, z1)
    np.testing.assert_allclose(np.asarray(g_shard), np.asarray(g_full), rtol=1e-4)


def test_local_negatives_differ_from_global(batch):
    """Per-replica negatives give a different (smaller-candidate-set) loss."""
    z0, z1 = map(jnp.asarray, batch)
    mesh = _data_mesh()

    local_fn = jax.shard_map(
        lambda a, b: ntxent_loss_local_negatives(a, b, axis_name="data"),
        mesh=mesh,
        in_specs=(P("data"), P("data")),
        out_specs=P(),
        check_vma=False,
    )
    local = float(jax.jit(local_fn)(z0, z1))
    global_ = float(ntxent_loss(z0, z1))
    assert local != pytest.approx(global_, rel=1e-3)

    # each replica's loss equals the naive loss on its own shard
    z0n, z1n = np.asarray(z0), np.asarray(z1)
    shard_losses = [
        naive_ntxent(z0n[i * 2 : (i + 1) * 2], z1n[i * 2 : (i + 1) * 2], 0.5)
        for i in range(8)
    ]
    assert local == pytest.approx(np.mean(shard_losses), rel=1e-5)


def test_local_equals_global_on_single_shard(batch):
    """On a 1-device mesh the local and global semantics coincide (SURVEY §7.3)."""
    z0, z1 = map(jnp.asarray, batch)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    local_fn = jax.shard_map(
        lambda a, b: ntxent_loss_local_negatives(a, b, axis_name="data"),
        mesh=mesh,
        in_specs=(P("data"), P("data")),
        out_specs=P(),
        check_vma=False,
    )
    sharded_fn = jax.shard_map(
        lambda a, b: ntxent_loss_sharded_rows(a, b, axis_name="data"),
        mesh=mesh,
        in_specs=(P("data"), P("data")),
        out_specs=P(),
        check_vma=False,
    )
    full = float(ntxent_loss(z0, z1))
    assert float(jax.jit(local_fn)(z0, z1)) == pytest.approx(full, rel=1e-5)
    assert float(jax.jit(sharded_fn)(z0, z1)) == pytest.approx(full, rel=1e-5)


def test_loss_decreases_when_views_align():
    """Sanity: identical views (perfect positives) give lower loss than random."""
    rng = np.random.RandomState(1)
    z = jnp.asarray(rng.randn(16, 8).astype(np.float32))
    aligned = float(ntxent_loss(z, z))
    random = float(ntxent_loss(z, jnp.asarray(rng.randn(16, 8).astype(np.float32))))
    assert aligned < random
