"""Epoch-compiled training (runtime.epoch_compile).

One XLA program per epoch with the dataset resident on device
(``parallel/steps.py:make_pretrain_epoch_fn``): the scan must consume the
same shuffled data order and per-step RNG streams as the dispatch-per-step
loop and produce numerically equivalent training (exact bitwise equality is
not promised — XLA fuses the scan body differently, reordering bfloat16
roundings).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from simclr_tpu.data.cifar import synthetic_dataset
from simclr_tpu.data.pipeline import epoch_index_matrix, epoch_permutation
from simclr_tpu.models.contrastive import ContrastiveModel
from simclr_tpu.ops.lars import lars, simclr_weight_decay_mask
from simclr_tpu.parallel.mesh import (
    DATA_AXIS,
    batch_sharding,
    create_mesh,
    put_row_sharded,
    put_tree,
    replicated_sharding,
)
from simclr_tpu.parallel.steps import (
    _sharded_rows_global_batch,
    make_pretrain_epoch_fn,
    make_pretrain_step,
)
from simclr_tpu.parallel.train_state import create_train_state
from simclr_tpu.utils.schedule import warmup_cosine_schedule

GLOBAL_BATCH = 32
DATASET = 64
STEPS_PER_EPOCH = 2
EPOCHS = 2


def _setup():
    mesh = create_mesh()
    model = ContrastiveModel(base_cnn="resnet18", d=128, bn_cross_replica_axis=DATA_AXIS)
    tx = lars(
        warmup_cosine_schedule(0.1, 20, 2),
        weight_decay=1e-4,
        weight_decay_mask=simclr_weight_decay_mask,
    )
    ds = synthetic_dataset("cifar10", "train", size=DATASET)
    return mesh, model, tx, ds


def _init_state(model, tx, mesh):
    state = create_train_state(
        model, tx, jax.random.key(7), jnp.zeros((2, 32, 32, 3), jnp.float32)
    )
    return jax.device_put(state, replicated_sharding(mesh))


@pytest.mark.slow
@pytest.mark.parametrize("residency", ["replicated", "sharded"])
def test_epoch_scan_matches_per_step_loop(residency):
    mesh, model, tx, ds = _setup()
    base_key = jax.random.key(11)

    step = make_pretrain_step(model, tx, mesh, temperature=0.5, strength=0.5)
    state_a = _init_state(model, tx, mesh)
    losses_a = []
    cur = 0
    for epoch in range(1, EPOCHS + 1):
        order = epoch_permutation(DATASET, 0, epoch)
        for i in range(STEPS_PER_EPOCH):
            idx = order[i * GLOBAL_BATCH : (i + 1) * GLOBAL_BATCH]
            batch = jax.device_put(ds.images[idx], batch_sharding(mesh))
            state_a, m = step(state_a, batch, jax.random.fold_in(base_key, cur))
            losses_a.append(float(m["loss"]))
            cur += 1

    epoch_fn = make_pretrain_epoch_fn(
        model, tx, mesh, temperature=0.5, strength=0.5, residency=residency
    )
    state_b = _init_state(model, tx, mesh)
    if residency == "replicated":
        images_all = jax.device_put(jnp.asarray(ds.images), replicated_sharding(mesh))
    else:
        images_all = put_row_sharded(ds.images, mesh)
        # the point of sharded residency: each data shard holds only its
        # N/n_data contiguous row block, not the whole dataset
        n_data = mesh.shape[DATA_AXIS]
        assert images_all.sharding.spec == P(DATA_AXIS)
        assert images_all.addressable_shards[0].data.shape[0] == DATASET // n_data
    losses_b = []
    cur = 0
    for epoch in range(1, EPOCHS + 1):
        idx_e = jnp.asarray(
            epoch_index_matrix(DATASET, 0, epoch, STEPS_PER_EPOCH, GLOBAL_BATCH)
        )
        state_b, hist = epoch_fn(state_b, images_all, idx_e, base_key, cur)
        losses_b.extend(float(x) for x in hist["loss"])
        cur += STEPS_PER_EPOCH

    # first epoch consumes identical inputs from identical params: losses of
    # its steps must agree tightly; later steps accumulate fusion-order drift
    np.testing.assert_allclose(losses_a, losses_b, rtol=2e-3)
    assert int(state_b.step) == EPOCHS * STEPS_PER_EPOCH
    pa = np.asarray(jax.tree.leaves(state_a.params)[0])
    pb = np.asarray(jax.tree.leaves(state_b.params)[0])
    np.testing.assert_allclose(pa, pb, atol=5e-3)


@pytest.mark.slow
@pytest.mark.parametrize("residency", ["replicated", "sharded"])
def test_supervised_epoch_compile_entrypoint(tmp_path, residency):
    from simclr_tpu.supervised import run_supervised
    from simclr_tpu.config import load_config

    cfg = load_config(
        "supervised_config",
        overrides=[
            "parameter.epochs=2",
            "experiment.batches=4",
            "parameter.warmup_epochs=0",
            "experiment.synthetic_data=true",
            "experiment.synthetic_size=64",
            "runtime.epoch_compile=true",
            f"runtime.dataset_residency={residency}",
            f"experiment.save_dir={tmp_path}",
        ],
    )
    summary = run_supervised(cfg)
    assert np.isfinite(summary["best_value"])
    # best-only policy still holds under the epoch-compiled path
    kept = [p for p in tmp_path.iterdir() if p.name.startswith("epoch=")]
    assert len(kept) == 1


@pytest.mark.slow
@pytest.mark.parametrize("residency", ["replicated", "sharded"])
def test_epoch_compile_entrypoint(tmp_path, residency):
    from simclr_tpu.main import run_pretrain
    from simclr_tpu.config import load_config

    cfg = load_config(
        "config",
        overrides=[
            "parameter.epochs=2",
            "experiment.batches=4",
            "parameter.warmup_epochs=0",
            "experiment.save_model_epoch=2",
            "experiment.synthetic_data=true",
            "experiment.synthetic_size=64",
            "runtime.epoch_compile=true",
            f"runtime.dataset_residency={residency}",
            f"experiment.save_dir={tmp_path}",
        ],
    )
    summary = run_pretrain(cfg)
    assert summary["steps"] == 2 * (64 // (4 * 8))
    assert np.isfinite(summary["final_loss"])
    assert (tmp_path / "epoch=2-cifar10").exists()


def test_epoch_compile_preconditions(monkeypatch, caplog):
    import logging

    import pytest

    from simclr_tpu.parallel import steps
    from simclr_tpu.parallel.steps import check_epoch_compile_preconditions

    # single-process, dataset >= one global batch: fine
    check_epoch_compile_preconditions(64, 32)
    with pytest.raises(ValueError, match="smaller than global batch"):
        check_epoch_compile_preconditions(16, 32)

    # profile_dir is incompatible with the scan path: warns, does not raise
    from simclr_tpu.utils.logging import get_logger

    monkeypatch.setattr(get_logger(), "propagate", True)  # let caplog see it
    with caplog.at_level(logging.WARNING):
        check_epoch_compile_preconditions(64, 32, profile_dir="/tmp/prof")
    assert any("profile_dir is ignored" in r.message for r in caplog.records)

    # multi-host is supported (put_replicated upload; identical per-process
    # index matrices): preconditions must NOT refuse on process count. The
    # real 2-process run is tests/test_launch.py::test_two_process_epoch_compile
    monkeypatch.setattr(steps.jax, "process_count", lambda: 2)
    check_epoch_compile_preconditions(64, 32)


def test_epoch_compile_hbm_preconditions():
    """HBM capacity math of the preflight: replicated residency counts the
    whole dataset per chip; sharded counts only the ceil(N/n_data) row
    block, so a dataset n_data x over the replicated budget still fits."""
    from simclr_tpu.parallel.steps import check_epoch_compile_preconditions

    # 64 rows x 100 B = 6400 B replicated per chip; budget 1000 B. Sharded
    # over 8 would hold 8 rows = 800 B, so the error must say so.
    with pytest.raises(ValueError, match="dataset_residency=sharded"):
        check_epoch_compile_preconditions(
            64, 32, dataset_bytes=6400, n_data_shards=8,
            residency="replicated", hbm_budget_bytes=1000,
        )
    # the same dataset under sharded residency fits that budget
    got = check_epoch_compile_preconditions(
        64, 32, dataset_bytes=6400, n_data_shards=8,
        residency="sharded", hbm_budget_bytes=1000,
    )
    assert got == 800
    # replicated within budget passes and reports the full footprint
    got = check_epoch_compile_preconditions(
        64, 32, dataset_bytes=6400, n_data_shards=8,
        residency="replicated", hbm_budget_bytes=10_000,
    )
    assert got == 6400
    # replicated over budget with no sharded escape hatch: no hint
    with pytest.raises(ValueError) as exc:
        check_epoch_compile_preconditions(
            64, 32, dataset_bytes=6400, n_data_shards=1,
            residency="replicated", hbm_budget_bytes=1000,
        )
    assert "dataset_residency=sharded" not in str(exc.value)
    # unknown residency is rejected before any capacity math
    with pytest.raises(ValueError, match="dataset_residency"):
        check_epoch_compile_preconditions(64, 32, residency="spilled")


def _gather_fn(mesh):
    return jax.jit(
        jax.shard_map(
            _sharded_rows_global_batch,
            mesh=mesh,
            in_specs=(P(DATA_AXIS), P()),
            out_specs=P(),
            check_vma=False,
        )
    )


def test_sharded_rows_gather_exact():
    """The psum-assembled global batch from row shards == a plain take on
    the host array, for uint8 rows and an arbitrary index set."""
    mesh = create_mesh()
    rows = np.random.default_rng(0).integers(
        0, 256, size=(DATASET, 4, 3), dtype=np.uint8
    )
    idx = np.asarray([5, 63, 0, 17, 42, 8, 8, 31], np.int32)
    sharded = put_row_sharded(rows, mesh)
    got = _gather_fn(mesh)(sharded, jnp.asarray(idx))
    np.testing.assert_array_equal(np.asarray(got), rows[idx])


def test_put_row_sharded_upload_feeds_only_addressable_rows(monkeypatch):
    """Multi-host residency preflight: the upload callback must be invoked
    once per ADDRESSABLE shard with exactly that shard's contiguous row
    block — never the full array per device. On a pod this is what keeps
    the epoch_compile upload O(N / n_processes) per host; the 2-process
    half of the claim is asserted end to end by scripts/multihost_dryrun.py
    (each process reports local_rows == its addressable row count)."""
    mesh = create_mesh()
    n_data = mesh.shape[DATA_AXIS]
    rows = np.arange(64 * 3, dtype=np.float32).reshape(64, 3)
    requested = []
    orig = jax.make_array_from_callback

    def spy(shape, sharding, cb):
        def wrapped(idx):
            requested.append(idx)
            return cb(idx)
        return orig(shape, sharding, wrapped)

    monkeypatch.setattr(jax, "make_array_from_callback", spy)
    sharded = put_row_sharded(rows, mesh)
    np.testing.assert_array_equal(np.asarray(sharded), rows)

    per_shard = 64 // n_data
    # one callback per addressable shard (jax may coalesce duplicates, so
    # compare as sets of row ranges), each asking for one shard-sized block
    got_blocks = {
        (idx[0].start or 0, idx[0].stop if idx[0].stop is not None else 64)
        for idx in requested
    }
    want_blocks = {
        (s.index[0].start or 0, s.index[0].stop)
        for s in sharded.addressable_shards
    }
    assert got_blocks == want_blocks
    for start, stop in got_blocks:
        assert stop - start == per_shard
    # and the addressable blocks tile this process's rows exactly once
    covered = sorted(got_blocks)
    assert covered[0][0] == 0 and covered[-1][1] == 64
    assert all(a[1] == b[0] for a, b in zip(covered, covered[1:]))


def test_put_tree_single_process_matches_device_put():
    """put_tree places a host-computed tree (a fresh state is placed by its
    own program, parallel/train_state.py): in a
    single process it must be exactly device_put — same values, same
    shardings — whether given one sharding for every leaf or a matching
    pytree of per-leaf shardings (the tensor-parallel layout case)."""
    mesh = create_mesh()
    tree = {
        "w": np.arange(12, dtype=np.float32).reshape(3, 4),
        "b": np.float32(2.5),
        "n": np.int32(7),
    }
    placed = put_tree(tree, replicated_sharding(mesh))
    want = jax.device_put(tree, replicated_sharding(mesh))
    for k in tree:
        np.testing.assert_array_equal(np.asarray(placed[k]), np.asarray(want[k]))
        assert placed[k].sharding == want[k].sharding, k

    shardings = {
        "w": batch_sharding(mesh),  # rows over the data axis
        "b": replicated_sharding(mesh),
        "n": replicated_sharding(mesh),
    }
    # 3 rows don't divide the 8-way axis; use a divisible leaf instead
    tree["w"] = np.arange(64, dtype=np.float32).reshape(8, 8)
    placed = put_tree(tree, shardings)
    np.testing.assert_array_equal(np.asarray(placed["w"]), tree["w"])
    assert placed["w"].sharding == batch_sharding(mesh)
    assert placed["b"].sharding == replicated_sharding(mesh)


def test_sharded_rows_gather_padded_tail():
    """N not divisible by n_data: put_row_sharded zero-pads the tail, and
    indices in [0, N) never touch the padding."""
    mesh = create_mesh()
    n = 61  # pads to 64 over 8 shards
    rows = np.random.default_rng(1).integers(0, 256, size=(n, 5), dtype=np.uint8)
    idx = np.asarray([60, 0, 59, 13, 7, 21, 34, 55], np.int32)
    sharded = put_row_sharded(rows, mesh)
    assert sharded.shape[0] == 64
    assert sharded.addressable_shards[0].data.shape[0] == 8
    got = _gather_fn(mesh)(sharded, jnp.asarray(idx))
    np.testing.assert_array_equal(np.asarray(got), rows[idx])
