"""Fused Pallas NT-Xent vs the plain-XLA loss: forward + gradient parity.

Runs in Pallas interpret mode on the CPU test backend; the same code
compiles natively on TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simclr_tpu.ops.ntxent import ntxent_loss
from simclr_tpu.ops.ntxent_pallas import _tile_and_pad, ntxent_loss_fused


def _views(n, d, seed=0):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.normal(size=(n, d)).astype(np.float32)),
        jnp.asarray(rng.normal(size=(n, d)).astype(np.float32)),
    )


class TestTileAndPad:
    def test_large_sizes_use_128_tiles(self):
        assert _tile_and_pad(1024) == (128, 1024)
        assert _tile_and_pad(204) == (128, 256)   # padded, never tiny tiles
        assert _tile_and_pad(129) == (128, 256)

    def test_small_sizes_single_aligned_tile(self):
        assert _tile_and_pad(64) == (64, 64)
        assert _tile_and_pad(6) == (8, 8)
        assert _tile_and_pad(96) == (96, 96)


class TestFusedForward:
    @pytest.mark.parametrize("n,d", [(8, 16), (32, 128)])
    def test_matches_reference(self, n, d):
        z0, z1 = _views(n, d)
        fused = float(ntxent_loss_fused(z0, z1, 0.5))
        ref = float(ntxent_loss(z0, z1, 0.5, "mean"))
        np.testing.assert_allclose(fused, ref, rtol=1e-5)

    def test_temperature(self):
        z0, z1 = _views(16, 32, seed=1)
        for t in (0.1, 1.0):
            np.testing.assert_allclose(
                float(ntxent_loss_fused(z0, z1, t)),
                float(ntxent_loss(z0, z1, t, "mean")),
                rtol=1e-5,
            )

    def test_under_jit(self):
        z0, z1 = _views(16, 32, seed=2)
        jitted = jax.jit(lambda a, b: ntxent_loss_fused(a, b, 0.5))
        np.testing.assert_allclose(
            float(jitted(z0, z1)), float(ntxent_loss(z0, z1, 0.5, "mean")), rtol=1e-5
        )


class TestFusedGradient:
    @pytest.mark.parametrize("n,d", [(8, 16), (32, 64)])
    def test_grads_match_autodiff(self, n, d):
        z0, z1 = _views(n, d, seed=3)
        g_fused = jax.grad(lambda a, b: ntxent_loss_fused(a, b, 0.5), argnums=(0, 1))(
            z0, z1
        )
        g_ref = jax.grad(
            lambda a, b: ntxent_loss(a, b, 0.5, "mean"), argnums=(0, 1)
        )(z0, z1)
        for a, b in zip(g_fused, g_ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
            )

    def test_grad_nonzero(self):
        z0, z1 = _views(8, 16, seed=4)
        g = jax.grad(lambda a: ntxent_loss_fused(a, z1, 0.5))(z0)
        assert float(jnp.abs(g).max()) > 0


class TestFusedInTrainStep:
    def test_fused_local_matches_plain_local(self):
        """fused=True on the 8-shard mesh == negatives='local' loss."""
        import numpy as np

        from simclr_tpu.ops.lars import lars
        from simclr_tpu.parallel.mesh import batch_sharding, create_mesh
        from simclr_tpu.parallel.steps import make_pretrain_step
        from simclr_tpu.parallel.train_state import create_train_state
        from tests.helpers import TinyContrastive as Tiny

        mesh = create_mesh()
        model = Tiny()
        tx = lars(0.1)
        images = np.random.default_rng(0).integers(
            0, 256, size=(32, 32, 32, 3), dtype=np.uint8
        )
        losses = {}
        for fused in (False, True):
            state = create_train_state(
                model, tx, jax.random.key(0), jnp.zeros((32, 32, 32, 3))
            )
            step = make_pretrain_step(
                model, tx, mesh, negatives="local", fused=fused
            )
            _, metrics = step(
                state,
                jax.device_put(images, batch_sharding(mesh)),
                jax.random.key(1),
            )
            losses[fused] = float(metrics["loss"])
        np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)

    def test_fused_ring_rejected(self):
        from simclr_tpu.ops.lars import lars
        from simclr_tpu.parallel.mesh import create_mesh
        from simclr_tpu.parallel.steps import make_pretrain_step

        mesh = create_mesh()
        with pytest.raises(ValueError, match="fused"):
            make_pretrain_step(None, lars(0.1), mesh, negatives="ring", fused=True)

    def test_fused_global_matches_gathered_in_step(self):
        """fused+global on the 8-shard mesh == the XLA gathered objective."""
        import numpy as np

        from simclr_tpu.ops.lars import lars
        from simclr_tpu.parallel.mesh import batch_sharding, create_mesh
        from simclr_tpu.parallel.steps import make_pretrain_step
        from simclr_tpu.parallel.train_state import create_train_state
        from tests.helpers import TinyContrastive as Tiny

        mesh = create_mesh()
        model = Tiny()
        tx = lars(0.1)
        images = np.random.default_rng(1).integers(
            0, 256, size=(32, 32, 32, 3), dtype=np.uint8
        )
        losses = {}
        for fused in (False, True):
            state = create_train_state(
                model, tx, jax.random.key(0), jnp.zeros((32, 32, 32, 3))
            )
            step = make_pretrain_step(
                model, tx, mesh, negatives="global", fused=fused
            )
            _, metrics = step(
                state,
                jax.device_put(images, batch_sharding(mesh)),
                jax.random.key(1),
            )
            losses[fused] = float(metrics["loss"])
        np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)


class TestMultihostNoop:
    def test_single_host_is_noop(self):
        from simclr_tpu.parallel.multihost import maybe_initialize_multihost

        assert maybe_initialize_multihost() is False


class TestFusedPaddingPath:
    @pytest.mark.parametrize("n,d", [(7, 16), (51, 32), (102, 16)])
    def test_odd_sizes_match_reference(self, n, d):
        """Sizes that are not tile multiples exercise the pad+mask path."""
        z0, z1 = _views(n, d, seed=7)
        np.testing.assert_allclose(
            float(ntxent_loss_fused(z0, z1, 0.5)),
            float(ntxent_loss(z0, z1, 0.5, "mean")),
            rtol=1e-5,
        )
        g_fused = jax.grad(lambda a: ntxent_loss_fused(a, z1, 0.5))(z0)
        g_ref = jax.grad(lambda a: ntxent_loss(a, z1, 0.5, "mean"))(z0)
        np.testing.assert_allclose(
            np.asarray(g_fused), np.asarray(g_ref), rtol=1e-4, atol=1e-6
        )


class TestFusedSharded:
    def _views(self, n=32, d=16, seed=10):
        rng = np.random.default_rng(seed)
        return (
            jnp.asarray(rng.normal(size=(n, d)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(n, d)).astype(np.float32)),
        )

    def _sharded(self, loss_fn):
        from jax.sharding import PartitionSpec as P

        from simclr_tpu.parallel.mesh import DATA_AXIS, create_mesh

        mesh = create_mesh()
        f = jax.shard_map(
            lambda a, b: loss_fn(a, b, DATA_AXIS, 0.5),
            mesh=mesh, in_specs=(P(DATA_AXIS), P(DATA_AXIS)), out_specs=P(),
            check_vma=False,
        )
        return f

    def test_forward_matches_gathered(self):
        from simclr_tpu.ops.ntxent import ntxent_loss_sharded_rows
        from simclr_tpu.ops.ntxent_pallas import ntxent_loss_fused_sharded

        z0, z1 = self._views()
        fused = float(jax.jit(self._sharded(ntxent_loss_fused_sharded))(z0, z1))
        ref = float(jax.jit(self._sharded(ntxent_loss_sharded_rows))(z0, z1))
        np.testing.assert_allclose(fused, ref, rtol=1e-5)

    def test_grads_match_gathered(self):
        from simclr_tpu.ops.ntxent import ntxent_loss_sharded_rows
        from simclr_tpu.ops.ntxent_pallas import ntxent_loss_fused_sharded

        z0, z1 = self._views(seed=11)
        g_fused = jax.jit(
            jax.grad(lambda a, b: self._sharded(ntxent_loss_fused_sharded)(a, b),
                     argnums=(0, 1))
        )(z0, z1)
        g_ref = jax.jit(
            jax.grad(lambda a, b: self._sharded(ntxent_loss_sharded_rows)(a, b),
                     argnums=(0, 1))
        )(z0, z1)
        for a, b in zip(g_fused, g_ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
            )
