"""Data layer tests: augmentations, CIFAR reader, epoch pipeline.

Augmentation correctness is checked against torchvision *semantics* computed
independently here (value ranges, determinism, distribution properties) — the
reference ships no tests at all (SURVEY §4), so these are the missing
contract for ``/root/reference/dataset.py:19-50``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simclr_tpu.data import (
    Dataset,
    EpochIterator,
    epoch_permutation,
    load_dataset,
    simclr_two_views,
    synthetic_dataset,
)
from simclr_tpu.data.augment import (
    adjust_brightness,
    adjust_contrast,
    adjust_hue,
    adjust_saturation,
    color_jitter,
    random_grayscale,
    random_hflip,
    random_resized_crop,
    simclr_augment_single,
    to_float,
)


def _image(seed=0, h=32, w=32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.random((h, w, 3)), dtype=jnp.float32)


class TestColorOps:
    def test_brightness_scales_linearly(self):
        img = _image()
        out = adjust_brightness(img, jnp.float32(0.5))
        np.testing.assert_allclose(out, np.clip(np.asarray(img) * 0.5, 0, 1), atol=1e-6)

    def test_contrast_zero_collapses_to_gray_mean(self):
        img = _image()
        out = adjust_contrast(img, jnp.float32(0.0))
        gray = np.asarray(img) @ np.array([0.299, 0.587, 0.114])
        assert np.allclose(out, gray.mean(), atol=1e-5)

    def test_saturation_zero_is_grayscale(self):
        img = _image()
        out = adjust_saturation(img, jnp.float32(0.0))
        assert np.allclose(out[..., 0], out[..., 1], atol=1e-6)
        assert np.allclose(out[..., 1], out[..., 2], atol=1e-6)

    def test_factor_one_is_identity(self):
        img = _image()
        for fn in (adjust_brightness, adjust_contrast, adjust_saturation):
            np.testing.assert_allclose(fn(img, jnp.float32(1.0)), img, atol=1e-5)

    def test_hue_zero_is_identity(self):
        img = _image()
        np.testing.assert_allclose(adjust_hue(img, jnp.float32(0.0)), img, atol=1e-5)

    def test_hue_full_turn_is_identity(self):
        img = _image()
        np.testing.assert_allclose(adjust_hue(img, jnp.float32(1.0)), img, atol=1e-4)

    def test_hue_half_turn_swaps_extremes(self):
        # pure red shifted half a turn becomes pure cyan
        red = jnp.zeros((2, 2, 3)).at[..., 0].set(1.0)
        out = adjust_hue(red, jnp.float32(0.5))
        np.testing.assert_allclose(out[0, 0], jnp.array([0.0, 1.0, 1.0]), atol=1e-5)

    def test_outputs_clipped_to_unit_range(self):
        img = _image()
        for fn, fac in [
            (adjust_brightness, 3.0),
            (adjust_contrast, 3.0),
            (adjust_saturation, 3.0),
        ]:
            out = fn(img, jnp.float32(fac))
            assert out.min() >= 0.0 and out.max() <= 1.0


class TestRandomOps:
    def test_hflip_flips_or_not(self):
        img = _image()
        flipped = 0
        for i in range(20):
            out = random_hflip(jax.random.key(i), img)
            if np.allclose(out, img[:, ::-1, :]):
                flipped += 1
            else:
                np.testing.assert_allclose(out, img)
        assert 3 < flipped < 17  # ~Binomial(20, 0.5)

    def test_grayscale_probability(self):
        img = _image()
        grays = sum(
            bool(
                np.allclose(
                    (g := random_grayscale(jax.random.key(i), img))[..., 0],
                    g[..., 1],
                )
            )
            for i in range(100)
        )
        assert 8 <= grays <= 36  # ~Binomial(100, 0.2)

    def test_crop_output_static_shape_and_range(self):
        img = _image()
        out = random_resized_crop(jax.random.key(0), img, out_size=32)
        assert out.shape == (32, 32, 3)
        assert out.min() >= -1e-4 and out.max() <= 1.0 + 1e-4

    def test_crop_identity_when_box_is_full_image(self):
        # a full-image crop box must reproduce the image exactly
        from simclr_tpu.data.augment import _axis_resize_weights

        img = _image()
        w = _axis_resize_weights(jnp.float32(0.0), jnp.float32(32.0), 32, 32)
        out = jnp.einsum("oh,hwc,pw->opc", w, img, w)
        np.testing.assert_allclose(out, img, atol=1e-5)

    def test_crop_matches_explicit_crop_then_resize(self):
        # interior AND border pixels must equal numpy crop-then-bilinear-resize
        from simclr_tpu.data.augment import _axis_resize_weights

        img = np.asarray(_image())
        top, left, ch, cw = 5, 9, 13, 17
        w_r = np.asarray(
            _axis_resize_weights(jnp.float32(top), jnp.float32(ch), 32, 32)
        )
        w_c = np.asarray(
            _axis_resize_weights(jnp.float32(left), jnp.float32(cw), 32, 32)
        )
        # sampling matrices must read ONLY inside the crop box
        assert np.all(w_r[:, :top] == 0) and np.all(w_r[:, top + ch :] == 0)
        assert np.all(w_c[:, :left] == 0) and np.all(w_c[:, left + cw :] == 0)

        # reference: crop with numpy, then the same clamped bilinear resize
        box = img[top : top + ch, left : left + cw]
        w_r_box = np.asarray(
            _axis_resize_weights(jnp.float32(0.0), jnp.float32(ch), 32, ch)
        )
        w_c_box = np.asarray(
            _axis_resize_weights(jnp.float32(0.0), jnp.float32(cw), 32, cw)
        )
        expected = np.einsum("oh,hwc,pw->opc", w_r_box, box, w_c_box)
        got = np.einsum("oh,hwc,pw->opc", w_r, img, w_c)
        np.testing.assert_allclose(got, expected, atol=1e-5)

    def test_crop_upsamples_subregion(self):
        # a gradient image: crops must stay within original value range
        grad = jnp.linspace(0, 1, 32 * 32 * 3).reshape(32, 32, 3)
        for i in range(5):
            out = random_resized_crop(jax.random.key(i), grad)
            assert out.min() >= -1e-3 and out.max() <= 1.0 + 1e-3

    def test_color_jitter_changes_image_and_stays_in_range(self):
        img = _image()
        out = color_jitter(jax.random.key(3), img, strength=0.5)
        assert not np.allclose(out, img)
        assert out.min() >= 0.0 and out.max() <= 1.0 + 1e-6

    def test_jitter_strength_zero_is_identity(self):
        img = _image()
        out = color_jitter(jax.random.key(0), img, strength=0.0)
        np.testing.assert_allclose(out, img, atol=1e-5)


def _scatter_add_weights(origin, size, out_size, in_size):
    """The scatter-add form ``_axis_resize_weights`` had before PR 32, kept
    here as its plain reference: two taps per row added into a zero matrix."""
    centers = origin + (jnp.arange(out_size, dtype=jnp.float32) + 0.5) * (
        size / out_size
    ) - 0.5
    centers = jnp.clip(centers, origin, origin + size - 1.0)
    i0 = jnp.floor(centers)
    frac = centers - i0
    i0 = jnp.clip(i0.astype(jnp.int32), 0, in_size - 1)
    i1 = jnp.clip(i0 + 1, 0, in_size - 1)
    rows = jnp.arange(out_size)
    weights = jnp.zeros((out_size, in_size), jnp.float32)
    weights = weights.at[rows, i0].add(1.0 - frac)
    weights = weights.at[rows, i1].add(frac)
    return weights


def _boxes(in_size, seed, n_random=256):
    """(origin, size) boxes on an axis of ``in_size`` pixels: every 1-pixel
    box, the whole axis, boxes touching the first, the last and both
    borders, and a seeded random sweep of in-bounds boxes."""
    boxes = [(o, 1) for o in range(in_size)]
    boxes += [(0, s) for s in range(1, in_size + 1)]
    boxes += [(in_size - s, s) for s in range(1, in_size + 1)]
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, in_size + 1, n_random)
    origins = np.floor(rng.random(n_random) * (in_size - sizes + 1))
    boxes += list(zip(origins, sizes))
    boxes = np.asarray(boxes, dtype=np.float32)
    return jnp.asarray(boxes[:, 0]), jnp.asarray(boxes[:, 1])


def _primitives(jaxpr):
    """Names of every primitive in ``jaxpr``, sub-jaxprs (pjit, the switch's
    branches) included."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


def _scatters(fn, *args):
    return {
        name
        for name in _primitives(jax.make_jaxpr(fn)(*args).jaxpr)
        if "scatter" in name
    }


def _uint8_batch(n, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).integers(0, 256, (n, 32, 32, 3)), dtype=jnp.uint8
    )


class TestAxisResizeWeights:
    """``_axis_resize_weights`` builds its matrix by comparison (PR 32); the
    scatter-add form it replaced is the reference it must equal to the bit."""

    @pytest.mark.parametrize(
        "out_size,in_size",
        [(32, 32), (32, 13), (16, 32), (24, 40), (32, 1), (1, 32)],
    )
    def test_equals_scatter_add_reference_bitwise(self, out_size, in_size):
        from simclr_tpu.data.augment import _axis_resize_weights

        origin, size = _boxes(in_size, seed=out_size * 100 + in_size)

        def batched(weights_fn):
            fn = jax.vmap(lambda o, s: weights_fn(o, s, out_size, in_size))
            return np.asarray(jax.jit(fn)(origin, size))

        got, want = batched(_axis_resize_weights), batched(_scatter_add_weights)
        assert got.shape == want.shape == (origin.shape[0], out_size, in_size)
        assert got.dtype == want.dtype == np.float32
        assert got.tobytes() == want.tobytes()
        np.testing.assert_allclose(got.sum(-1), 1.0, atol=1e-6)
        # at most two taps a row, and none outside the box
        assert (np.count_nonzero(got, axis=-1) <= 2).all()
        src = np.arange(in_size)
        lo = np.asarray(origin)[:, None, None]
        hi = lo + np.asarray(size)[:, None, None]
        assert (got[np.broadcast_to((src < lo) | (src >= hi), got.shape)] == 0).all()

    def test_equals_scatter_add_reference_unbatched(self):
        # one box at a time, not under vmap: size 1, the whole axis, both
        # borders, an interior box, and an up- and a down-sampling resize
        from simclr_tpu.data.augment import _axis_resize_weights

        for origin, size, out_size, in_size in [
            (0.0, 1.0, 32, 32), (31.0, 1.0, 32, 32), (0.0, 32.0, 32, 32),
            (0.0, 7.0, 32, 32), (25.0, 7.0, 32, 32), (5.0, 13.0, 32, 32),
            (3.0, 20.0, 8, 32), (0.0, 5.0, 32, 5),
        ]:
            got = _axis_resize_weights(
                jnp.float32(origin), jnp.float32(size), out_size, in_size
            )
            want = _scatter_add_weights(
                jnp.float32(origin), jnp.float32(size), out_size, in_size
            )
            assert np.asarray(got).tobytes() == np.asarray(want).tobytes(), (
                origin, size, out_size, in_size,
            )

    def test_clipped_border_sums_both_taps_into_one_column(self):
        # a 1-pixel box: every row's two taps fall on the one column
        from simclr_tpu.data.augment import _axis_resize_weights

        w = np.asarray(
            _axis_resize_weights(jnp.float32(31.0), jnp.float32(1.0), 32, 32)
        )
        assert (w[:, 31] == 1.0).all() and (w[:, :31] == 0.0).all()

    @pytest.mark.parametrize("chain", ["single", "two_views", "step"])
    def test_augmentation_traces_no_scatter(self, chain):
        """The serial op cannot come back unseen: neither the vmapped
        per-example chain nor the step's two-view wrapper traces a scatter."""
        from simclr_tpu.parallel.steps import _augment_two_views

        images, key = _uint8_batch(8), jax.random.key(0)
        if chain == "single":
            found = _scatters(
                jax.vmap(simclr_augment_single), jax.random.split(key, 8), to_float(images)
            )
        elif chain == "two_views":
            found = _scatters(simclr_two_views, key, images)
        else:
            found = _scatters(
                lambda k, x: _augment_two_views(k, x, 0.5, 32), key, images
            )
        assert found == set()

    def test_scatter_detector_sees_the_reference_form(self, monkeypatch):
        # the check above is not vacuous: with the scatter-add form patched
        # back in, the same walk finds it, inside a jitted call's sub-jaxpr
        from simclr_tpu.data import augment

        monkeypatch.setattr(augment, "_axis_resize_weights", _scatter_add_weights)
        found = _scatters(
            jax.jit(jax.vmap(simclr_augment_single)),
            jax.random.split(jax.random.key(0), 8), to_float(_uint8_batch(8)),
        )
        assert "scatter-add" in found

    def test_whole_view_equals_scatter_add_reference_bytes(self, monkeypatch):
        # the whole view, not only the matrix: 64 seeded keys through
        # simclr_augment_single with and without the reference patched in
        from simclr_tpu.data import augment

        images = to_float(_uint8_batch(64, seed=3))
        keys = jax.random.split(jax.random.key(32), 64)

        def views():
            return np.asarray(jax.jit(jax.vmap(simclr_augment_single))(keys, images))

        got = views()
        monkeypatch.setattr(augment, "_axis_resize_weights", _scatter_add_weights)
        want = views()
        assert got.shape == (64, 32, 32, 3)
        assert got.tobytes() == want.tobytes()
        assert not np.array_equal(got[0], got[1])


class TestTwoViews:
    def test_views_are_independent_and_deterministic(self):
        imgs = jnp.asarray(
            np.random.default_rng(0).integers(0, 256, (4, 32, 32, 3)), dtype=jnp.uint8
        )
        v0, v1 = simclr_two_views(jax.random.key(0), imgs)
        assert v0.shape == v1.shape == (4, 32, 32, 3)
        assert not np.allclose(v0, v1)  # independent draws
        v0b, v1b = simclr_two_views(jax.random.key(0), imgs)
        np.testing.assert_allclose(v0, v0b)  # same key -> same views
        np.testing.assert_allclose(v1, v1b)

    def test_per_example_keys_differ(self):
        imgs = jnp.tile(
            jnp.asarray(
                np.random.default_rng(1).integers(0, 256, (1, 32, 32, 3)),
                dtype=jnp.uint8,
            ),
            (3, 1, 1, 1),
        )
        v0, _ = simclr_two_views(jax.random.key(0), imgs)
        # identical inputs must get different augmentations per example
        assert not np.allclose(v0[0], v0[1])

    def test_to_float_matches_totensor(self):
        img = jnp.asarray([[[0, 128, 255]]], dtype=jnp.uint8)
        np.testing.assert_allclose(
            to_float(img), jnp.asarray([[[0.0, 128 / 255, 1.0]]]), atol=1e-7
        )

    def test_single_view_jits_without_recompile_guards(self):
        img = jnp.zeros((32, 32, 3), jnp.float32)
        fn = jax.jit(simclr_augment_single, static_argnames=())
        out = fn(jax.random.key(0), img)
        assert out.shape == (32, 32, 3)


class TestCifarReader:
    def test_missing_data_raises_without_synthetic(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            load_dataset("cifar10", data_dir=str(tmp_path))

    def test_synthetic_fallback(self, tmp_path):
        ds = load_dataset(
            "cifar10", data_dir=str(tmp_path), synthetic_ok=True, synthetic_size=256
        )
        assert ds.synthetic
        assert ds.images.shape == (256, 32, 32, 3)
        assert ds.images.dtype == np.uint8
        assert ds.labels.dtype == np.int32
        assert ds.num_classes == 10

    def test_pickle_roundtrip_cifar10(self, tmp_path):
        # write a miniature archive in the real format and read it back
        import pickle

        base = tmp_path / "cifar-10-batches-py"
        base.mkdir()
        rng = np.random.default_rng(0)
        chw = rng.integers(0, 256, (20, 3072), dtype=np.uint8)
        for i in range(1, 6):
            with open(base / f"data_batch_{i}", "wb") as f:
                pickle.dump(
                    {b"data": chw[(i - 1) * 4 : i * 4], b"labels": [i % 10] * 4}, f
                )
        ds = load_dataset("cifar10", data_dir=str(tmp_path))
        assert ds.images.shape == (20, 32, 32, 3)
        # CHW-flat row 0, channel 0, pixel (0,0) -> NHWC [0,0,0,0]
        assert ds.images[0, 0, 0, 0] == chw[0, 0]
        assert ds.images[0, 0, 0, 1] == chw[0, 1024]  # G plane offset
        assert not ds.synthetic

    def test_synthetic_is_class_conditional(self):
        ds = synthetic_dataset("cifar10", "train", size=200)
        # same-class images correlate more than cross-class (shared
        # prototype vs instance-specific field+texture)
        a = ds.images[ds.labels == 0].astype(np.float32)
        same = np.corrcoef(a[0].ravel(), a[1].ravel())[0, 1]
        b = ds.images[ds.labels == 1].astype(np.float32)
        cross = np.corrcoef(a[0].ravel(), b[0].ravel())[0, 1]
        assert same > cross + 0.2
        # the instance content is LOW-FREQUENCY (view-stable under crops),
        # not iid: after removing the shared class prototype, variation
        # across 4x4 upsample cells must dominate variation within a cell
        # (iid noise would make them equal — the measured-collapse design
        # this generator replaced)
        resid = a[0] - a.mean(0)
        cells = resid.reshape(8, 4, 8, 4, 3)
        within_cell = cells.std(axis=(1, 3)).mean()
        across_cells = cells.mean(axis=(1, 3)).std()
        assert across_cells > 2.0 * within_cell, (across_cells, within_cell)

    def test_bad_name_raises(self):
        with pytest.raises(ValueError):
            load_dataset("imagenet")


class TestEpochIterator:
    def _dataset(self, n=64):
        return Dataset(
            images=np.arange(n, dtype=np.uint8)[:, None, None, None]
            * np.ones((1, 32, 32, 3), np.uint8),
            labels=np.arange(n, dtype=np.int32) % 10,
            name="cifar10",
            split="train",
        )

    def test_drop_last_truncation(self):
        it = EpochIterator(self._dataset(50), global_batch=16, seed=7)
        assert it.steps_per_epoch == 3  # 50 // 16, reference drop_last parity
        batches = list(it.batches(epoch=0))
        assert len(batches) == 3
        assert all(b["image"].shape == (16, 32, 32, 3) for b in batches)

    def test_epoch_reshuffle_is_deterministic_and_distinct(self):
        p0 = epoch_permutation(100, seed=7, epoch=0)
        p0b = epoch_permutation(100, seed=7, epoch=0)
        p1 = epoch_permutation(100, seed=7, epoch=1)
        np.testing.assert_array_equal(p0, p0b)
        assert not np.array_equal(p0, p1)

    def test_epoch_covers_dataset_without_replacement(self):
        it = EpochIterator(self._dataset(64), global_batch=16, seed=0)
        seen = np.concatenate(
            [b["image"][:, 0, 0, 0] for b in it.batches(epoch=0)]
        )
        assert len(np.unique(seen)) == 64

    def test_sharded_device_put(self):
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
        sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("data")
        )
        it = EpochIterator(
            self._dataset(64), global_batch=16, seed=0, sharding=sharding
        )
        batch = next(it.batches(epoch=0))
        assert isinstance(batch["image"], jax.Array)
        assert batch["image"].sharding.is_equivalent_to(sharding, 4)
        # each of the 8 devices holds 2 rows
        assert batch["image"].addressable_shards[0].data.shape[0] == 2

    def test_batch_too_large_raises(self):
        with pytest.raises(ValueError):
            EpochIterator(self._dataset(8), global_batch=16)
