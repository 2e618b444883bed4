"""Tests for the SPMD mesh + compiled train steps on the 8-device CPU mesh.

Per SURVEY.md §4: multi-device logic is validated with
``--xla_force_host_platform_device_count=8`` (set in conftest), no TPU needed.
Models here are tiny stand-ins with the same Flax API surface as the real
ResNet encoder (encode/__call__ methods, params + batch_stats collections,
cross-replica BN axis) so the step machinery is exercised without the
compile cost of a full ResNet.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from simclr_tpu.ops.lars import lars, simclr_weight_decay_mask
from simclr_tpu.parallel.mesh import (
    DATA_AXIS,
    MeshSpec,
    batch_sharding,
    create_mesh,
    local_batch_size,
    validate_per_device_batch,
)
from simclr_tpu.parallel.steps import (
    make_encode_step,
    make_pretrain_step,
    make_supervised_eval_step,
    make_supervised_step,
)
from simclr_tpu.parallel.train_state import TrainState, create_train_state, param_count


from tests.helpers import TinyContrastive, TinySupervised, random_images as _images


def _make_state(model, tx, batch=16):
    sample = jnp.zeros((batch, 32, 32, 3), jnp.float32)
    return create_train_state(model, tx, jax.random.key(0), sample)


# ---------------------------------------------------------------------------
# Mesh
# ---------------------------------------------------------------------------

class TestMesh:
    def test_default_mesh_uses_all_devices(self):
        mesh = create_mesh()
        assert mesh.shape[DATA_AXIS] == 8
        assert mesh.shape["model"] == 1

    def test_spec_resolution(self):
        assert MeshSpec(-1, 1).resolve(8) == (8, 1)
        assert MeshSpec(4, 2).resolve(8) == (4, 2)
        assert MeshSpec(2, -1).resolve(8) == (2, 4)
        with pytest.raises(ValueError):
            MeshSpec(3, 1).resolve(8)
        with pytest.raises(ValueError):
            MeshSpec(-1, -1).resolve(8)

    def test_batch_size_helpers(self):
        mesh = create_mesh()
        assert local_batch_size(64, mesh) == 8
        assert validate_per_device_batch(4, mesh) == 32
        with pytest.raises(ValueError):
            local_batch_size(12, mesh)

    def test_single_device_mesh(self):
        mesh = create_mesh(devices=jax.devices()[:1])
        assert mesh.shape[DATA_AXIS] == 1

    def test_async_collective_flags_tpu_gated_and_idempotent(self):
        """enable_async_collective_flags mutates XLA_FLAGS only on a TPU
        platform (unknown --xla_tpu_* flags are fatal on CPU jaxlib) and
        never duplicates a flag on repeat calls — main.py invokes it every
        run when comm_overlap=async. Platform detection is env-based: the
        function must run BEFORE backend init, so it can never consult
        jax.default_backend()."""
        from simclr_tpu.parallel.mesh import (
            ASYNC_COLLECTIVE_XLA_FLAGS,
            enable_async_collective_flags,
        )

        # off-TPU: a no-op, env untouched
        env = {"JAX_PLATFORMS": "cpu"}
        assert enable_async_collective_flags(env) is False
        assert "XLA_FLAGS" not in env

        # TPU: all flags appended, preserving whatever was already set
        env = {"JAX_PLATFORMS": "tpu", "XLA_FLAGS": "--xla_dump_to=/tmp/d"}
        assert enable_async_collective_flags(env) is True
        for flag in ASYNC_COLLECTIVE_XLA_FLAGS:
            assert env["XLA_FLAGS"].count(flag) == 1, flag
        assert env["XLA_FLAGS"].startswith("--xla_dump_to=/tmp/d")

        # idempotent: a second call adds nothing
        before = env["XLA_FLAGS"]
        assert enable_async_collective_flags(env) is True
        assert env["XLA_FLAGS"] == before

        # a pod worker without JAX_PLATFORMS still counts as TPU
        env = {"TPU_NAME": "v4-8"}
        assert enable_async_collective_flags(env) is True
        assert "XLA_FLAGS" in env


# ---------------------------------------------------------------------------
# Pretrain step
# ---------------------------------------------------------------------------

class TestPretrainStep:
    def _run(self, negatives, mesh=None, n_steps=2, batch=16):
        mesh = mesh or create_mesh()
        model = TinyContrastive(bn_cross_replica_axis=DATA_AXIS)
        tx = lars(0.1, weight_decay=1e-4, weight_decay_mask=simclr_weight_decay_mask)
        state = _make_state(model, tx, batch)
        step = make_pretrain_step(
            model, tx, mesh, temperature=0.5, strength=0.5, negatives=negatives
        )
        sharding = batch_sharding(mesh)
        losses = []
        for i in range(n_steps):
            images = jax.device_put(_images(batch, seed=i), sharding)
            state, metrics = step(state, images, jax.random.key(100 + i))
            losses.append(float(metrics["loss"]))
        return state, losses

    def test_global_negatives_runs_and_updates(self):
        state, losses = self._run("global")
        assert int(state.step) == 2
        assert all(np.isfinite(losses))
        # loss magnitude sanity: ln(2N-1) ballpark for random embeddings
        assert 0.0 < losses[0] < 20.0

    def test_local_negatives_runs(self):
        _, losses = self._run("local")
        assert all(np.isfinite(losses))

    def test_global_equals_local_on_single_device_mesh(self):
        """With one data shard the global candidate set IS the local batch."""
        mesh1 = create_mesh(devices=jax.devices()[:1])
        _, loss_g = self._run("global", mesh=mesh1, n_steps=1)
        _, loss_l = self._run("local", mesh=mesh1, n_steps=1)
        np.testing.assert_allclose(loss_g[0], loss_l[0], rtol=1e-5)

    def test_deterministic(self):
        _, a = self._run("global", n_steps=1)
        _, b = self._run("global", n_steps=1)
        np.testing.assert_allclose(a, b, rtol=1e-6)

    def test_global_loss_sees_cross_shard_negatives(self):
        """Global-negative loss must differ from local-negative loss on a
        multi-shard mesh (more negatives -> different objective)."""
        _, loss_g = self._run("global", n_steps=1)
        _, loss_l = self._run("local", n_steps=1)
        assert abs(loss_g[0] - loss_l[0]) > 1e-4

    def test_params_and_stats_change(self):
        mesh = create_mesh()
        model = TinyContrastive(bn_cross_replica_axis=DATA_AXIS)
        tx = lars(0.1)
        state = _make_state(model, tx)
        before = jax.tree.map(np.asarray, (state.params, state.batch_stats))
        step = make_pretrain_step(model, tx, mesh)
        images = jax.device_put(_images(16), batch_sharding(mesh))
        state, _ = step(state, images, jax.random.key(0))
        after = jax.tree.map(np.asarray, (state.params, state.batch_stats))
        diffs = jax.tree.leaves(
            jax.tree.map(lambda x, y: float(np.abs(x - y).max()), before, after)
        )
        assert max(diffs) > 0


# ---------------------------------------------------------------------------
# Supervised steps
# ---------------------------------------------------------------------------

class TestSupervisedStep:
    def test_train_and_eval(self):
        mesh = create_mesh()
        model = TinySupervised(bn_cross_replica_axis=DATA_AXIS)
        tx = lars(0.1)
        state = _make_state(model, tx)
        train_step = make_supervised_step(model, tx, mesh)
        eval_step = make_supervised_eval_step(model, mesh)
        sharding = batch_sharding(mesh)

        labels_np = np.arange(16, dtype=np.int32) % 10
        images = jax.device_put(_images(16), sharding)
        labels = jax.device_put(labels_np, sharding)
        state, metrics = train_step(state, images, labels, jax.random.key(0))
        assert np.isfinite(float(metrics["loss"]))
        assert 0.0 <= float(metrics["accuracy"]) <= 1.0
        assert int(state.step) == 1

        valid = jax.device_put(np.ones(16, np.float32), sharding)
        totals = eval_step(state.params, state.batch_stats, images, labels, valid)
        assert float(totals["count"]) == 16.0
        assert 0.0 <= float(totals["correct"]) <= 16.0
        assert np.isfinite(float(totals["sum_loss"]))

    def test_eval_matches_unsharded_forward(self):
        """psum'd totals == single-device full-batch computation."""
        mesh = create_mesh()
        model = TinySupervised(bn_cross_replica_axis=DATA_AXIS)
        tx = lars(0.1)
        state = _make_state(model, tx)
        eval_step = make_supervised_eval_step(model, mesh)
        images_np = _images(16)
        labels_np = np.arange(16, dtype=np.int32) % 10
        sharding = batch_sharding(mesh)
        totals = eval_step(
            state.params,
            state.batch_stats,
            jax.device_put(images_np, sharding),
            jax.device_put(labels_np, sharding),
            jax.device_put(np.ones(16, np.float32), sharding),
        )
        logits = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            images_np.astype(np.float32) / 255.0,
            train=False,
        )
        expected_correct = float(np.sum(np.argmax(np.asarray(logits), -1) == labels_np))
        assert float(totals["correct"]) == expected_correct

    def test_eval_tail_mask_ignores_padding(self):
        """A non-divisible validation set, zero-padded to the static batch
        shape with valid=0 on the padding, must yield identical totals to the
        real rows alone — the single-code-path replacement for the old eager
        host-side tail pass (VERDICT r1 #6)."""
        mesh = create_mesh()
        model = TinySupervised(bn_cross_replica_axis=DATA_AXIS)
        tx = lars(0.1)
        state = _make_state(model, tx)
        eval_step = make_supervised_eval_step(model, mesh)
        sharding = batch_sharding(mesh)

        n_real, batch = 13, 16  # 13 real rows padded up to one global batch
        images_np = _images(batch)
        labels_np = np.arange(batch, dtype=np.int32) % 10
        images_np[n_real:] = 0  # padding rows: arbitrary content
        valid = np.zeros(batch, np.float32)
        valid[:n_real] = 1.0
        totals = eval_step(
            state.params,
            state.batch_stats,
            jax.device_put(images_np, sharding),
            jax.device_put(labels_np, sharding),
            jax.device_put(valid, sharding),
        )
        assert float(totals["count"]) == float(n_real)
        logits = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            images_np[:n_real].astype(np.float32) / 255.0,
            train=False,
        )
        expected_correct = float(
            np.sum(np.argmax(np.asarray(logits), -1) == labels_np[:n_real])
        )
        assert float(totals["correct"]) == expected_correct


# ---------------------------------------------------------------------------
# Encode step
# ---------------------------------------------------------------------------

class TestEncodeStep:
    def test_encoder_vs_full(self):
        mesh = create_mesh()
        model = TinyContrastive()
        tx = lars(0.1)
        state = _make_state(model, tx)
        enc_h = make_encode_step(model, mesh, use_full_encoder=False)
        enc_z = make_encode_step(model, mesh, use_full_encoder=True)
        images = jax.device_put(_images(16), batch_sharding(mesh))
        h = enc_h(state.params, state.batch_stats, images)
        z = enc_z(state.params, state.batch_stats, images)
        assert h.shape == (16, 16)
        assert z.shape == (16, 8)

    def test_param_count(self):
        model = TinyContrastive()
        state = _make_state(model, lars(0.1))
        n = 32 * 32 * 3 * 16 + 16  # dense1
        n += 16 + 16  # bn scale/bias
        n += 16 * 8 + 8  # dense2
        assert param_count(state.params) == n


class TestForwardMode:
    def test_concat_runs_and_differs_from_two_pass(self):
        mesh = create_mesh()
        model = TinyContrastive(bn_cross_replica_axis=DATA_AXIS)
        tx = lars(0.1)
        images = _images(16, seed=9)
        losses = {}
        for mode in ("two_pass", "concat"):
            state = _make_state(model, tx)
            step = make_pretrain_step(model, tx, mesh, forward_mode=mode)
            state, metrics = step(
                state, jax.device_put(images, batch_sharding(mesh)), jax.random.key(3)
            )
            losses[mode] = float(metrics["loss"])
            assert np.isfinite(losses[mode])
        # joint-BN vs per-view BN statistics -> small but nonzero difference
        assert losses["two_pass"] != losses["concat"]

    def test_bad_mode_rejected(self):
        mesh = create_mesh()
        with pytest.raises(ValueError, match="forward_mode"):
            make_pretrain_step(None, lars(0.1), mesh, forward_mode="bogus")


class TestRemat:
    def test_remat_matches_plain(self):
        """jax.checkpoint must not change values, only the backward schedule."""
        mesh = create_mesh()
        model = TinyContrastive(bn_cross_replica_axis=DATA_AXIS)
        tx = lars(0.1)
        images = _images(16, seed=11)
        results = {}
        for remat in (False, True):
            state = _make_state(model, tx)
            step = make_pretrain_step(model, tx, mesh, remat=remat)
            state, metrics = step(
                state, jax.device_put(images, batch_sharding(mesh)), jax.random.key(5)
            )
            results[remat] = (
                float(metrics["loss"]),
                np.asarray(jax.tree.leaves(state.params)[0]),
            )
        np.testing.assert_allclose(results[True][0], results[False][0], rtol=1e-6)
        np.testing.assert_allclose(results[True][1], results[False][1], rtol=1e-5)


class TestStepTimer:
    def test_throughput_summary(self):
        from simclr_tpu.utils.profiling import StepTimer

        timer = StepTimer(global_batch=32)
        x = jnp.ones((4,))
        for _ in range(StepTimer.WARMUP + 4):
            timer.tick(x)
        summary = timer.summary()
        assert summary["steps"] == 4
        assert summary["imgs_per_sec"] > 0
        assert summary["imgs_per_sec_per_chip"] == summary["imgs_per_sec"] / 8

    def test_no_ticks_safe(self):
        from simclr_tpu.utils.profiling import StepTimer

        assert StepTimer(32).summary()["steps"] == 0

    def test_warmup_ticks_are_untimed(self):
        from simclr_tpu.utils.profiling import StepTimer

        timer = StepTimer(32)
        for _ in range(StepTimer.WARMUP):
            timer.tick(jnp.ones((4,)))
        assert timer.summary()["steps"] == 0

    def test_pause_excludes_interval(self):
        import time

        from simclr_tpu.utils.profiling import StepTimer

        timer = StepTimer(global_batch=32)
        x = jnp.ones((4,))
        for _ in range(StepTimer.WARMUP + 2):
            timer.tick(x)
        timer.pause(x)
        time.sleep(0.5)  # simulated checkpoint save
        timer.resume()
        timer.tick(x)
        summary = timer.summary()
        assert summary["steps"] == 3
        # the paused 0.5s must not count: 3 trivial steps take far less
        assert summary["seconds"] < 0.4, summary
