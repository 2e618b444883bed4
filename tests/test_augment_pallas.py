"""Fused Pallas two-view augmentation (``ops/augment_pallas.py``).

The contract under test:

- pixel parity: the fused kernel (CPU interpret mode here, like the
  ``ntxent_pallas`` tests) reproduces the XLA chain per view within float
  tolerance, across tile-padding edge cases (batch 1, non-multiple-of-8
  batches, multi-tile batches, out_size != 32) and both input dtypes;
- randomness single-sourcing: the fused path draws its parameters from the
  SAME samplers as the XLA path (``_view_keys`` → ``_sample_crop_box`` /
  ``jitter_params``), pinned by monkeypatch spies — a kernel that grows its
  own sampler would silently fork the augmentation distribution;
- the ``augment_impl=xla`` default is BITWISE-identical to the pre-knob
  pipeline (the once-per-image ``to_float`` hoist is value-preserving);
- loss parity: ``augment_impl=fused`` trains within 5e-2 of xla at equal
  seeds for the dp, dp×tp and supervised steps;
- config validation rejects unknown impls in both conf paths.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from simclr_tpu.data import augment as aug_mod
from simclr_tpu.data.augment import simclr_augment_single, simclr_two_views, to_float
from simclr_tpu.ops.augment_pallas import (
    AUGMENT_IMPLS,
    _tile_and_pad,
    fused_one_view,
    fused_two_views,
    validate_impl,
)
from simclr_tpu.ops.lars import lars, simclr_weight_decay_mask
from simclr_tpu.parallel.mesh import (
    DATA_AXIS,
    batch_sharding,
    create_mesh,
    replicated_sharding,
)
from simclr_tpu.parallel.steps import make_pretrain_step
from simclr_tpu.parallel.train_state import create_train_state
from tests.helpers import TinyContrastive, random_images

PIXEL_ATOL = 1e-5
LOSS_ATOL = 5e-2

GLOBAL_BATCH = 16


# ---------------------------------------------------------------------------
# knob + tiling plumbing
# ---------------------------------------------------------------------------

def test_validate_impl():
    assert AUGMENT_IMPLS == ("xla", "fused")
    for impl in AUGMENT_IMPLS:
        assert validate_impl(impl) == impl
    with pytest.raises(ValueError, match="augment_impl must be xla|fused"):
        validate_impl("pallas")


def test_tile_and_pad():
    # small batches: one tile, rounded to a multiple of 8
    assert _tile_and_pad(1) == (8, 8)
    assert _tile_and_pad(8) == (8, 8)
    assert _tile_and_pad(13) == (16, 16)
    # large batches: 32-row tiles, padded to the tile grid
    assert _tile_and_pad(32) == (32, 32)
    assert _tile_and_pad(33) == (32, 64)
    assert _tile_and_pad(64) == (32, 64)


def test_config_validates_augment_impl():
    from simclr_tpu.config import ConfigError, check_pretrain_conf, load_config

    base = [
        "experiment.synthetic_data=true",
        "experiment.synthetic_size=64",
        "experiment.batches=4",
    ]
    for impl in AUGMENT_IMPLS:
        check_pretrain_conf(
            load_config("config", overrides=base + [f"runtime.augment_impl={impl}"])
        )
    with pytest.raises(ConfigError, match="augment_impl"):
        check_pretrain_conf(
            load_config("config", overrides=base + ["runtime.augment_impl=bogus"])
        )

    from simclr_tpu.config import check_supervised_conf

    with pytest.raises(ConfigError, match="augment_impl"):
        check_supervised_conf(
            load_config(
                "supervised_config",
                overrides=base + ["runtime.augment_impl=bogus"],
            )
        )


def test_builders_reject_bad_impl():
    from simclr_tpu.parallel.steps import make_supervised_step
    from simclr_tpu.parallel.tp import make_pretrain_step_tp

    mesh = create_mesh()
    model = TinyContrastive(bn_cross_replica_axis=DATA_AXIS)
    tx = _tx()
    with pytest.raises(ValueError, match="augment_impl"):
        make_pretrain_step(
            model, tx, mesh, temperature=0.5, strength=0.5, augment_impl="bogus"
        )
    with pytest.raises(ValueError, match="augment_impl"):
        make_supervised_step(model, tx, mesh, strength=0.5, augment_impl="bogus")
    with pytest.raises(ValueError, match="augment_impl"):
        make_pretrain_step_tp(
            model, tx, mesh, temperature=0.5, strength=0.5, augment_impl="bogus"
        )


# ---------------------------------------------------------------------------
# pixel parity (CPU interpret mode) + tile-padding edge cases
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "n",
    [1, 5, pytest.param(33, marks=pytest.mark.slow)],
    # single row, non-multiple-of-8, two tiles (grid > 1)
)
def test_two_view_pixel_parity(n):
    images = random_images(n, seed=n)
    rng = jax.random.key(7)
    want0, want1 = simclr_two_views(rng, images, 0.5, 32)
    got0, got1 = fused_two_views(rng, jnp.asarray(images), 0.5, 32)
    assert got0.dtype == jnp.float32 and got0.shape == (n, 32, 32, 3)
    np.testing.assert_allclose(np.asarray(got0), np.asarray(want0), atol=PIXEL_ATOL)
    np.testing.assert_allclose(np.asarray(got1), np.asarray(want1), atol=PIXEL_ATOL)


def test_two_view_pixel_parity_out_size_16():
    images = random_images(6, seed=2)
    rng = jax.random.key(3)
    want0, want1 = simclr_two_views(rng, images, 0.5, 16)
    got0, got1 = fused_two_views(rng, jnp.asarray(images), 0.5, 16)
    assert got0.shape == (6, 16, 16, 3)
    np.testing.assert_allclose(np.asarray(got0), np.asarray(want0), atol=PIXEL_ATOL)
    np.testing.assert_allclose(np.asarray(got1), np.asarray(want1), atol=PIXEL_ATOL)


@pytest.mark.slow
def test_one_view_parity_supervised_key_schedule():
    """``fused_one_view`` matches the supervised XLA branch: ``split(rng, n)``
    per-image keys through the same single-view chain."""
    images = random_images(9, seed=4)
    rng = jax.random.key(11)
    keys = jax.random.split(rng, 9)
    aug = jax.vmap(simclr_augment_single, in_axes=(0, 0, None, None))
    want = aug(keys, to_float(jnp.asarray(images)), 0.5, 32)
    got = fused_one_view(rng, jnp.asarray(images), 0.5, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=PIXEL_ATOL)


@pytest.mark.slow
def test_float_input_parity():
    """float32 input skips the in-VMEM dequant scale but must still match."""
    images = to_float(jnp.asarray(random_images(5, seed=8)))
    rng = jax.random.key(21)
    want0, want1 = simclr_two_views(rng, images, 0.5, 32)
    got0, got1 = fused_two_views(rng, images, 0.5, 32)
    np.testing.assert_allclose(np.asarray(got0), np.asarray(want0), atol=PIXEL_ATOL)
    np.testing.assert_allclose(np.asarray(got1), np.asarray(want1), atol=PIXEL_ATOL)


def test_fused_rejects_non_rgb():
    with pytest.raises(ValueError, match="RGB"):
        fused_two_views(jax.random.key(0), jnp.zeros((4, 32, 32, 1), jnp.uint8))


# ---------------------------------------------------------------------------
# randomness single-sourcing: the fused path calls THE samplers
# ---------------------------------------------------------------------------

def test_fused_draws_from_the_xla_samplers(monkeypatch):
    """Monkeypatched spies on ``data/augment.py``'s samplers must observe the
    fused path's parameter draws — the kernel consumes (does not re-derive)
    the one true augmentation distribution."""
    calls = {"_view_keys": 0, "_sample_crop_box": 0, "jitter_params": 0}

    def spy(name):
        orig = getattr(aug_mod, name)

        def wrapped(*args, **kwargs):
            calls[name] += 1
            return orig(*args, **kwargs)

        return wrapped

    for name in calls:
        monkeypatch.setattr(aug_mod, name, spy(name))

    n = 3
    fused_two_views(jax.random.key(0), jnp.asarray(random_images(n, seed=0)))
    # vmap traces each sampler once per view (not per example)
    assert calls["_view_keys"] >= 2
    assert calls["_sample_crop_box"] >= 2
    assert calls["jitter_params"] >= 2


def test_fused_tracks_a_patched_sampler(monkeypatch):
    """Deeper than call-counting: forcing the crop sampler to a constant box
    must change BOTH impls to the same deterministic crop — proof the kernel
    consumes the sampler's output rather than a parallel reimplementation."""

    def fixed_box(key, height, width):
        return (
            jnp.float32(4.0), jnp.float32(6.0),
            jnp.float32(16.0), jnp.float32(20.0),
        )

    monkeypatch.setattr(aug_mod, "_sample_crop_box", fixed_box)
    images = random_images(4, seed=1)
    rng = jax.random.key(5)
    # bypass simclr_two_views' jit cache (it closed over the unpatched
    # sampler in earlier tests): rebuild the vmapped chain directly
    imgs_f = to_float(jnp.asarray(images))
    keys = jax.random.split(rng, 8)
    aug = jax.vmap(simclr_augment_single, in_axes=(0, 0, None, None))
    want0 = aug(keys[:4], imgs_f, 0.5, 32)
    got0, _ = fused_two_views(rng, jnp.asarray(images), 0.5, 32)
    np.testing.assert_allclose(np.asarray(got0), np.asarray(want0), atol=PIXEL_ATOL)


# ---------------------------------------------------------------------------
# augment_impl=xla is bitwise the pre-knob pipeline
# ---------------------------------------------------------------------------

def test_xla_impl_bitwise_identical_to_pre_knob_chain():
    """The to_float hoist (once per image, not once per view) is
    value-preserving: the pre-knob chain — per-view ``to_float`` inside the
    single-view function — reproduces today's ``simclr_two_views`` output
    BITWISE on uint8 input."""
    images = jnp.asarray(random_images(7, seed=9))
    rng = jax.random.key(13)

    def pre_knob_two_views(key, imgs, strength, out_size):
        n = imgs.shape[0]
        keys = jax.random.split(key, 2 * n)
        aug = jax.vmap(
            lambda k, im: simclr_augment_single(
                k, to_float(im), strength, out_size
            ),
            in_axes=(0, 0),
        )
        return aug(keys[:n], imgs), aug(keys[n:], imgs)

    want0, want1 = jax.jit(
        pre_knob_two_views, static_argnames=("strength", "out_size")
    )(rng, images, strength=0.5, out_size=32)
    got0, got1 = simclr_two_views(rng, images, 0.5, 32)
    np.testing.assert_array_equal(np.asarray(got0), np.asarray(want0))
    np.testing.assert_array_equal(np.asarray(got1), np.asarray(want1))


# ---------------------------------------------------------------------------
# fused trains like xla at equal seeds
# ---------------------------------------------------------------------------

def _tx():
    return lars(0.1, weight_decay=1e-4, weight_decay_mask=simclr_weight_decay_mask)


def _init_state(model, tx, mesh):
    state = create_train_state(
        model, tx, jax.random.key(7), jnp.zeros((2, 32, 32, 3), jnp.float32)
    )
    return jax.device_put(state, replicated_sharding(mesh))


def _dp_step_losses(augment_impl, n_steps=3):
    mesh = create_mesh()
    model = TinyContrastive(bn_cross_replica_axis=DATA_AXIS)
    tx = _tx()
    step = make_pretrain_step(
        model, tx, mesh, temperature=0.5, strength=0.5,
        augment_impl=augment_impl,
    )
    state = _init_state(model, tx, mesh)
    losses = []
    for i in range(n_steps):
        images = jax.device_put(
            random_images(GLOBAL_BATCH, seed=i), batch_sharding(mesh)
        )
        state, metrics = step(state, images, jax.random.key(100 + i))
        losses.append(float(metrics["loss"]))
    return losses


def test_dp_step_loss_parity_fused_vs_xla():
    xla = _dp_step_losses("xla")
    fused = _dp_step_losses("fused")
    assert all(np.isfinite(xla)) and all(np.isfinite(fused))
    np.testing.assert_allclose(fused, xla, atol=LOSS_ATOL)


@pytest.mark.slow
def test_tp_step_loss_parity_fused_vs_xla():
    """dp×tp (data=4, model=2): the fused kernel runs inside the shard_map
    step body and must track the xla trajectory."""
    from simclr_tpu.models.contrastive import ContrastiveModel
    from simclr_tpu.parallel.mesh import MeshSpec
    from simclr_tpu.parallel.tp import make_pretrain_step_tp, tp_state_shardings

    mesh = create_mesh(MeshSpec(data=4, model=2))
    model = ContrastiveModel(
        base_cnn="resnet18", d=128, dtype=jnp.float32,
        bn_cross_replica_axis=DATA_AXIS,
    )
    tx = _tx()
    losses = {}
    for impl in AUGMENT_IMPLS:
        step = make_pretrain_step_tp(
            model, tx, mesh, temperature=0.5, strength=0.5, augment_impl=impl
        )
        state = create_train_state(
            model, tx, jax.random.key(7), jnp.zeros((2, 32, 32, 3), jnp.float32)
        )
        state = jax.device_put(state, tp_state_shardings(mesh, state))
        run = []
        for i in range(2):
            images = jax.device_put(
                random_images(GLOBAL_BATCH, seed=i), batch_sharding(mesh)
            )
            state, metrics = step(state, images, jax.random.key(100 + i))
            run.append(float(metrics["loss"]))
        losses[impl] = run
    assert all(np.isfinite(losses["fused"]))
    np.testing.assert_allclose(losses["fused"], losses["xla"], atol=LOSS_ATOL)


@pytest.mark.slow
def test_supervised_step_fused_vs_xla_loss_parity():
    """The single-view supervised path: fused matches xla at equal seeds."""
    from simclr_tpu.parallel.steps import make_supervised_step

    mesh = create_mesh()
    from tests.helpers import TinySupervised

    model = TinySupervised()
    tx = _tx()
    rng = np.random.default_rng(0)
    labels_np = rng.integers(0, 10, size=GLOBAL_BATCH).astype(np.int32)
    losses = {}
    for impl in AUGMENT_IMPLS:
        step = make_supervised_step(
            model, tx, mesh, strength=0.5, augment_impl=impl
        )
        state = _init_state(model, tx, mesh)
        images = jax.device_put(
            random_images(GLOBAL_BATCH, seed=1), batch_sharding(mesh)
        )
        labels = jax.device_put(jnp.asarray(labels_np), batch_sharding(mesh))
        state, metrics = step(state, images, labels, jax.random.key(5))
        losses[impl] = float(metrics["loss"])
    assert np.isfinite(losses["fused"])
    np.testing.assert_allclose(losses["fused"], losses["xla"], atol=LOSS_ATOL)
