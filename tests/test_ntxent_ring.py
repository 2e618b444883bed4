"""Ring NT-Xent must match the gathered global-negatives loss exactly
(forward AND gradients), on the 8-shard CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from simclr_tpu.ops.ntxent import ntxent_loss, ntxent_loss_sharded_rows
from simclr_tpu.ops.ntxent_ring import ntxent_loss_ring
from simclr_tpu.parallel.mesh import DATA_AXIS, create_mesh


def _views(n=32, d=16, seed=0):
    rng = np.random.default_rng(seed)
    return (
        rng.normal(size=(n, d)).astype(np.float32),
        rng.normal(size=(n, d)).astype(np.float32),
    )


def _sharded_loss(loss_fn, z0, z1, temperature=0.5):
    mesh = create_mesh()
    f = jax.shard_map(
        lambda a, b: loss_fn(a, b, DATA_AXIS, temperature),
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(f)(z0, z1)


class TestRingForward:
    def test_matches_gathered(self):
        z0, z1 = _views()
        ring = float(_sharded_loss(ntxent_loss_ring, z0, z1))
        gathered = float(_sharded_loss(ntxent_loss_sharded_rows, z0, z1))
        np.testing.assert_allclose(ring, gathered, rtol=1e-5)

    def test_matches_unsharded_reference(self):
        """Ring over 8 shards == plain full-batch NT-Xent on one device."""
        z0, z1 = _views(seed=3)
        ring = float(_sharded_loss(ntxent_loss_ring, z0, z1))
        full = float(ntxent_loss(jnp.asarray(z0), jnp.asarray(z1), 0.5, "mean"))
        np.testing.assert_allclose(ring, full, rtol=1e-5)

    @pytest.mark.parametrize("temperature", [0.1, 1.0])
    def test_temperatures(self, temperature):
        z0, z1 = _views(seed=4)
        ring = float(_sharded_loss(ntxent_loss_ring, z0, z1, temperature))
        full = float(
            ntxent_loss(jnp.asarray(z0), jnp.asarray(z1), temperature, "mean")
        )
        np.testing.assert_allclose(ring, full, rtol=1e-5)


class TestRingGradients:
    def _grad(self, loss_fn, z0, z1):
        mesh = create_mesh()

        def local(a, b):
            return loss_fn(a, b, DATA_AXIS, 0.5)

        f = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS)), out_specs=P(),
            check_vma=False,
        )
        return jax.jit(jax.grad(lambda a, b: f(a, b)))(z0, z1)

    def test_grads_match_gathered(self):
        z0, z1 = _views(seed=5)
        g_ring = self._grad(ntxent_loss_ring, jnp.asarray(z0), jnp.asarray(z1))
        g_gather = self._grad(
            ntxent_loss_sharded_rows, jnp.asarray(z0), jnp.asarray(z1)
        )
        np.testing.assert_allclose(
            np.asarray(g_ring), np.asarray(g_gather), rtol=1e-4, atol=1e-6
        )

    def test_grads_match_unsharded(self):
        z0, z1 = _views(seed=6)
        g_ring = self._grad(ntxent_loss_ring, jnp.asarray(z0), jnp.asarray(z1))
        g_full = jax.grad(
            lambda a, b: ntxent_loss(a, b, 0.5, "mean")
        )(jnp.asarray(z0), jnp.asarray(z1))
        np.testing.assert_allclose(
            np.asarray(g_ring), np.asarray(g_full), rtol=1e-4, atol=1e-6
        )


class TestRingInTrainStep:
    def test_pretrain_step_ring_negatives(self):
        """The full train step runs with negatives='ring' and matches the
        'global' objective's loss on the same inputs."""
        from simclr_tpu.ops.lars import lars
        from simclr_tpu.parallel.mesh import batch_sharding
        from simclr_tpu.parallel.steps import make_pretrain_step
        from simclr_tpu.parallel.train_state import create_train_state
        from tests.helpers import TinyContrastive as Tiny

        mesh = create_mesh()
        model = Tiny()
        tx = lars(0.1)
        images = np.random.default_rng(0).integers(
            0, 256, size=(16, 32, 32, 3), dtype=np.uint8
        )
        losses = {}
        for mode in ("ring", "global"):
            state = create_train_state(
                model, tx, jax.random.key(0), jnp.zeros((16, 32, 32, 3))
            )
            step = make_pretrain_step(model, tx, mesh, negatives=mode)
            _, metrics = step(
                state, jax.device_put(images, batch_sharding(mesh)), jax.random.key(1)
            )
            losses[mode] = float(metrics["loss"])
        np.testing.assert_allclose(losses["ring"], losses["global"], rtol=1e-5)
