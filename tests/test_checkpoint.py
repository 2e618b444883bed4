"""Orbax checkpoint round-trip + naming-scheme + integrity-sidecar tests."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simclr_tpu.ops.lars import lars
from simclr_tpu.parallel.train_state import TrainState
from simclr_tpu.utils.checkpoint import (
    CheckpointCorruptionError,
    checkpoint_digest,
    checkpoint_name,
    delete_checkpoint,
    digest_path,
    epoch_of,
    latest_checkpoint,
    list_checkpoints,
    restore_checkpoint,
    restore_checkpoint_with_fallback,
    save_checkpoint,
    verify_checkpoint,
)


def _tiny_state(seed=0) -> TrainState:
    params = {"dense": {"kernel": jnp.ones((4, 2)) * seed, "bias": jnp.zeros(2)}}
    tx = lars(0.1)
    return TrainState(
        step=jnp.asarray(3, jnp.int32),
        params=params,
        batch_stats={"bn": {"mean": jnp.ones(2)}},
        opt_state=tx.init(params),
    )


class TestNaming:
    def test_checkpoint_name_strips_pt(self):
        assert checkpoint_name(100, "cifar10.pt") == "epoch=100-cifar10"
        assert checkpoint_name(7, "model") == "epoch=7-model"

    def test_epoch_of(self):
        assert epoch_of("/x/epoch=200-cifar10") == 200
        assert epoch_of("/x/not-a-ckpt") == -1

    def test_list_sorted_by_epoch(self, tmp_path):
        for e in (100, 20, 3):
            os.makedirs(tmp_path / f"epoch={e}-m")
        os.makedirs(tmp_path / "unrelated")
        got = [epoch_of(p) for p in list_checkpoints(str(tmp_path))]
        assert got == [3, 20, 100]

    def test_list_missing_dir(self):
        assert list_checkpoints("/nonexistent/dir") == []

    def test_preempt_sorts_after_boundary_of_same_epoch(self, tmp_path):
        # a "-preempt" checkpoint holds strictly more steps than the plain
        # boundary checkpoint of the same epoch, so it must enumerate later —
        # including for stems that sort lexicographically AFTER "preempt"
        # (the supervised stem: "epoch=2-preempt…" < "epoch=2-supervised…")
        for name in (
            "epoch=2-supervised-cifar10",
            "epoch=2-supervised-cifar10-preempt",
            "epoch=1-supervised-cifar10",
            "epoch=3-supervised-cifar10",
        ):
            os.makedirs(tmp_path / name)
        got = [os.path.basename(p) for p in list_checkpoints(str(tmp_path))]
        assert got == [
            "epoch=1-supervised-cifar10",
            "epoch=2-supervised-cifar10",
            "epoch=2-supervised-cifar10-preempt",
            "epoch=3-supervised-cifar10",
        ]
        # adversarial stem ordering: preempt tag still wins within the epoch
        for name in ("epoch=5-a-preempt", "epoch=5-z"):
            os.makedirs(tmp_path / name)
        got = [os.path.basename(p) for p in list_checkpoints(str(tmp_path))]
        assert got[-2:] == ["epoch=5-z", "epoch=5-a-preempt"]


class TestRoundTrip:
    def test_save_restore_with_target(self, tmp_path):
        state = _tiny_state(seed=2)
        path = str(tmp_path / "epoch=3-m")
        save_checkpoint(path, state)
        restored = restore_checkpoint(path, _tiny_state(seed=0))
        assert int(restored.step) == 3
        np.testing.assert_array_equal(
            np.asarray(restored.params["dense"]["kernel"]),
            np.asarray(state.params["dense"]["kernel"]),
        )

    def test_restore_raw(self, tmp_path):
        state = _tiny_state(seed=5)
        path = str(tmp_path / "epoch=1-m")
        save_checkpoint(path, state)
        raw = restore_checkpoint(path)
        assert int(raw["step"]) == 3
        np.testing.assert_array_equal(
            np.asarray(raw["params"]["dense"]["kernel"]), np.full((4, 2), 5.0)
        )

    def test_latest_and_delete(self, tmp_path):
        for e in (1, 2):
            save_checkpoint(str(tmp_path / f"epoch={e}-m"), _tiny_state(e))
        latest = latest_checkpoint(str(tmp_path))
        assert epoch_of(latest) == 2
        delete_checkpoint(latest)
        assert epoch_of(latest_checkpoint(str(tmp_path))) == 1


class TestCrossTopologyRestore:
    def test_mesh_saved_checkpoint_restores_on_one_device(self, tmp_path):
        """A checkpoint saved with arrays sharded over the 8-device mesh must
        load in a single-device process (train on a pod, serve/eval on one
        chip): the raw restore path materializes to host numpy instead of
        re-applying the saved shardings."""
        import subprocess
        import sys
        import textwrap

        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(np.asarray(jax.devices()), ("data",))
        params = {
            "dense": {
                "kernel": jax.device_put(
                    jnp.arange(16, dtype=jnp.float32).reshape(8, 2),
                    NamedSharding(mesh, PartitionSpec("data", None)),
                ),
                "bias": jnp.zeros(2),
            }
        }
        tx = lars(0.1)
        state = TrainState(
            step=jnp.asarray(3, jnp.int32),
            params=params,
            batch_stats={"bn": {"mean": jnp.ones(2)}},
            opt_state=tx.init(params),
        )
        path = str(tmp_path / "epoch=3-m")
        save_checkpoint(path, state)

        code = textwrap.dedent(
            f"""
            import jax
            import numpy as np
            from simclr_tpu.utils.checkpoint import restore_checkpoint
            assert jax.device_count() == 1, jax.device_count()
            raw = restore_checkpoint({path!r})
            assert all(type(l) is np.ndarray for l in jax.tree.leaves(raw))
            assert int(raw["step"]) == 3
            kernel = raw["params"]["dense"]["kernel"]
            np.testing.assert_array_equal(
                kernel, np.arange(16, dtype=np.float32).reshape(8, 2)
            )
            print("OK")
            """
        )
        env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}
        proc = subprocess.run(
            [sys.executable, "-c", code],
            env=env,
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert proc.returncode == 0, proc.stderr
        assert "OK" in proc.stdout


class TestIntegrity:
    def test_save_writes_sidecar_and_verify_round_trips(self, tmp_path):
        path = str(tmp_path / "epoch=1-m")
        save_checkpoint(path, _tiny_state())
        sidecar = digest_path(path)
        assert os.path.exists(sidecar)
        with open(sidecar) as f:
            recorded = f.read().split()
        assert recorded[0] == checkpoint_digest(path)
        assert len(recorded[0]) == 64
        assert verify_checkpoint(path) is True
        restore_checkpoint(path, _tiny_state())  # verified load succeeds

    def test_corruption_is_detected(self, tmp_path):
        path = str(tmp_path / "epoch=1-m")
        save_checkpoint(path, _tiny_state())
        # flip bytes in some checkpoint payload file
        victim = None
        for root, _dirs, names in os.walk(path):
            for name in names:
                full = os.path.join(root, name)
                if os.path.getsize(full) > 0:
                    victim = full
        assert victim is not None
        with open(victim, "r+b") as f:
            first = f.read(1)
            f.seek(0)
            f.write(bytes([first[0] ^ 0xFF]))
        with pytest.raises(CheckpointCorruptionError, match="sha256"):
            verify_checkpoint(path)
        with pytest.raises(CheckpointCorruptionError):
            restore_checkpoint(path)

    def test_truncation_is_detected(self, tmp_path):
        path = str(tmp_path / "epoch=1-m")
        save_checkpoint(path, _tiny_state())
        largest = max(
            (os.path.join(r, n) for r, _d, ns in os.walk(path) for n in ns),
            key=os.path.getsize,
        )
        with open(largest, "r+b") as f:
            f.truncate(max(os.path.getsize(largest) - 1, 0))
        with pytest.raises(CheckpointCorruptionError):
            verify_checkpoint(path)

    def test_legacy_checkpoint_without_sidecar_loads_with_warning(self, tmp_path):
        path = str(tmp_path / "epoch=1-m")
        save_checkpoint(path, _tiny_state(seed=4))
        os.unlink(digest_path(path))
        assert verify_checkpoint(path) is False  # legacy: absent, not corrupt
        raw = restore_checkpoint(path)  # warn-only, still restores
        np.testing.assert_array_equal(
            np.asarray(raw["params"]["dense"]["kernel"]), np.full((4, 2), 4.0)
        )

    def test_unparseable_sidecar_is_corruption(self, tmp_path):
        path = str(tmp_path / "epoch=1-m")
        save_checkpoint(path, _tiny_state())
        with open(digest_path(path), "w") as f:
            f.write("not-a-digest\n")
        with pytest.raises(CheckpointCorruptionError, match="unparseable"):
            verify_checkpoint(path)

    def test_sidecars_never_enumerate_as_checkpoints(self, tmp_path):
        for e in (1, 2):
            save_checkpoint(str(tmp_path / f"epoch={e}-m"), _tiny_state(e))
        listed = list_checkpoints(str(tmp_path))
        assert [epoch_of(p) for p in listed] == [1, 2]
        assert not any(p.endswith(".sha256") for p in listed)
        assert epoch_of(latest_checkpoint(str(tmp_path))) == 2

    def test_delete_removes_sidecar(self, tmp_path):
        path = str(tmp_path / "epoch=1-m")
        save_checkpoint(path, _tiny_state())
        delete_checkpoint(path)
        assert not os.path.exists(path)
        assert not os.path.exists(digest_path(path))

    def test_digest_depends_on_content_and_layout(self, tmp_path):
        a, b = str(tmp_path / "epoch=1-m"), str(tmp_path / "epoch=2-m")
        save_checkpoint(a, _tiny_state(seed=1))
        save_checkpoint(b, _tiny_state(seed=2))
        assert checkpoint_digest(a) != checkpoint_digest(b)


class TestRestoreFallback:
    def test_empty_dir_is_a_fresh_run(self, tmp_path):
        assert restore_checkpoint_with_fallback(str(tmp_path)) == (None, None)

    def test_newest_verified_wins(self, tmp_path):
        for e in (1, 2):
            save_checkpoint(str(tmp_path / f"epoch={e}-m"), _tiny_state(e))
        restored, path = restore_checkpoint_with_fallback(
            str(tmp_path), _tiny_state(0)
        )
        assert epoch_of(path) == 2
        np.testing.assert_array_equal(
            np.asarray(restored.params["dense"]["kernel"]), np.full((4, 2), 2.0)
        )

    def test_corrupt_newest_falls_back_to_older(self, tmp_path):
        from simclr_tpu.supervisor.faults import corrupt_checkpoint_bytes

        for e in (1, 2):
            save_checkpoint(str(tmp_path / f"epoch={e}-m"), _tiny_state(e))
        corrupt_checkpoint_bytes(str(tmp_path / "epoch=2-m"))
        restored, path = restore_checkpoint_with_fallback(
            str(tmp_path), _tiny_state(0)
        )
        assert epoch_of(path) == 1
        np.testing.assert_array_equal(
            np.asarray(restored.params["dense"]["kernel"]), np.full((4, 2), 1.0)
        )

    def test_all_corrupt_raises(self, tmp_path):
        from simclr_tpu.supervisor.faults import corrupt_checkpoint_bytes

        for e in (1, 2):
            path = str(tmp_path / f"epoch={e}-m")
            save_checkpoint(path, _tiny_state(e))
            corrupt_checkpoint_bytes(path)
        with pytest.raises(CheckpointCorruptionError, match="all 2 checkpoint"):
            restore_checkpoint_with_fallback(str(tmp_path), _tiny_state(0))
