"""Pallas TPU kernel: fused two-view SimCLR augmentation in one VMEM pass.

The XLA augmentation path (``data/augment.py`` vmapped per example) is
correct but traffic-heavy: each view materializes the per-example bilinear
weight matrices and several batch-sized float32 temporaries in HBM, and the
uint8 source rows are re-read per view. This kernel reads a tile of resident
**uint8** rows into VMEM once and emits BOTH augmented float32 views in a
single pass: in-VMEM dequant (``to_float`` semantics, uint8 never touches
HBM as float), the two bilinear crop/resize contractions, horizontal flip,
the random-order color jitter, and grayscale — no per-stage HBM
intermediates. Same discipline as ``ops/ntxent_pallas.py``: keep the hot
tensor in VMEM, never round-trip HBM.

Randomness stays single-sourced and bit-identical to the XLA path: every
stochastic parameter (crop box, flip/jitter/grayscale gates, jitter factors
and op order) is sampled OUTSIDE the kernel by the exact samplers the XLA
path uses — ``_view_keys`` → ``_sample_crop_box`` / ``jitter_params`` in
``data/augment.py``, consumed in the same key order — so the distribution
tests keep measuring the one true sampler and a knob flip changes the
schedule, not the draw. The kernel is a pure deterministic function of
(uint8 tile, per-view parameter rows).

Layout, chosen for what Mosaic compiles (v5e, jax 0.9 / libtpu 0.0.34):

  * images enter and leave as ``(batch, H, W*3)`` — the free reshape of
    NHWC, lane-dense with the three channels interleaved along the lanes —
    in blocks of ``tile`` images; a ``fori_loop`` walks the tile one image
    at a time, so every value in the body is a 2-D ``(32, 96)`` array;
  * the per-view parameter rows sit in SMEM and are read as scalars
    (``lax.cond`` / ``lax.switch`` on them run exactly one jitter op per
    slot instead of computing all four and selecting);
  * uint8 widens through int32 (Mosaic has no uint8 -> float32 cast);
  * the bilinear weights are rebuilt from the 4 crop-box scalars via iota
    comparisons, the form ``_axis_resize_weights`` has too (here on float
    index planes, there on int32 indices; equal values, including the
    clipped ``i0 == i1`` edge where both taps land on one column and sum
    to 1). The column resize is one ``(W*3, out*3)`` matmul
    per channel whose result is that channel broadcast over each pixel's
    three lanes, so the color ops see r, g and b as same-shaped dense planes
    and no lane is ever sliced or shuffled; the horizontal flip is folded
    into that matrix by reversing its output index.

Compiled by Mosaic on TPU. ``interpret=True`` is used on the CPU backend
only, which is how the CPU tests run the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from simclr_tpu.data import augment as _aug

# runtime.augment_impl universe — config validation and the builders both
# import this tuple so the error message and the dispatch can't drift
AUGMENT_IMPLS = ("xla", "fused")

# per-view parameter row: crop box (top, left, h, w) + flip/apply/gray gates
# + jitter factors (brightness, contrast, saturation, hue) + the 4-slot op
# order (the _JITTER_PERMS row, exact small ints in float32)
_N_PARAMS = 15


def validate_impl(impl: str) -> str:
    if impl not in AUGMENT_IMPLS:
        raise ValueError(
            f"augment_impl must be {'|'.join(AUGMENT_IMPLS)}, got {impl!r}"
        )
    return impl


def _interpret() -> bool:
    # the Pallas interpreter is for the CPU backend only (tests); every other
    # backend compiles the kernel or fails
    return jax.default_backend() == "cpu"


def _tile_and_pad(n: int) -> tuple[int, int]:
    """(tile, padded_n) over the batch axis.

    Small batches round up to a multiple of 8 so one tile covers everything;
    large batches tile at 32 images (96 KiB of uint8 source and 384 KiB of
    float32 output per view, double-buffered — far inside VMEM). Padded
    tail rows carry zero parameter rows (a degenerate but finite crop) and
    are sliced off after the call.
    """
    tile = 32 if n >= 32 else -(-n // 8) * 8
    return tile, -(-n // tile) * tile


def _pad_rows(x: jnp.ndarray, n_pad: int) -> jnp.ndarray:
    n = x.shape[0]
    if n == n_pad:
        return x
    pad_widths = [(0, n_pad - n)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad_widths)


# ---------------------------------------------------------------------------
# parameter precompute (plain JAX, outside the kernel)
# ---------------------------------------------------------------------------

def _view_params(
    keys: jnp.ndarray, height: int, width: int, strength: float
) -> jnp.ndarray:
    """(n, _N_PARAMS) float32 parameter rows for one view.

    Consumes each per-view key exactly as ``simclr_augment_single`` does —
    ``_view_keys`` then crop box / flip gate / apply gate / jitter params /
    grayscale gate — through module-attribute lookups, so monkeypatched spy
    tests observe the same sampler calls the XLA path makes.
    """

    def one(key):
        k_crop, k_flip, k_apply, k_jitter, k_gray = _aug._view_keys(key)
        top, left, crop_h, crop_w = _aug._sample_crop_box(k_crop, height, width)
        flip = jax.random.uniform(k_flip) < _aug._HFLIP_P
        apply = jax.random.uniform(k_apply) < _aug._JITTER_APPLY_P
        f_b, f_c, f_s, f_h, perm_idx = _aug.jitter_params(k_jitter, strength)
        gray = jax.random.uniform(k_gray) < _aug._GRAYSCALE_P
        perm = jnp.asarray(_aug._JITTER_PERMS)[perm_idx].astype(jnp.float32)
        head = jnp.stack(
            [
                top, left, crop_h, crop_w,
                flip.astype(jnp.float32),
                apply.astype(jnp.float32),
                f_b, f_c, f_s, f_h,
                gray.astype(jnp.float32),
            ]
        ).astype(jnp.float32)
        return jnp.concatenate([head, perm])

    return jax.vmap(one)(keys)


# ---------------------------------------------------------------------------
# in-kernel ops (one image at a time, 2-D lane-dense values)
# ---------------------------------------------------------------------------

def _dot(a, b):
    # float32 passes on the MXU: the bilinear taps and the pixels keep their
    # mantissa, as they do in the CPU reference
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _float_iota(shape, dimension: int) -> jnp.ndarray:
    return jax.lax.broadcasted_iota(jnp.int32, shape, dimension).astype(
        jnp.float32
    )


def _bilinear(dst, src, origin, size, out_size: int, in_size: int):
    """``_axis_resize_weights(origin, size)[dst, src]``, elementwise over
    same-shaped float index arrays.

    The same comparison form as the XLA path's: both taps are selected by
    equality with the source index and added, so the clipped ``i0 == i1``
    border case sums the two taps into one column. Indices stay float32
    here (a Mosaic constraint); they are small exact integers, so ``==`` is
    exact.
    """
    centers = origin + (dst + 0.5) * (size / out_size) - 0.5
    centers = jnp.clip(centers, origin, origin + size - 1.0)
    floor = jnp.floor(centers)
    frac = centers - floor
    i0 = jnp.clip(floor, 0.0, in_size - 1.0)
    i1 = jnp.clip(i0 + 1.0, 0.0, in_size - 1.0)
    return jnp.where(src == i0, 1.0 - frac, 0.0) + jnp.where(
        src == i1, frac, 0.0
    )


def _luma(r, g, b):
    w = _aug._GRAY_WEIGHTS  # ITU-R 601, the XLA path's constants
    return r * w[0] + g * w[1] + b * w[2]


def _augment_image(x, p, out_size: int, height: int, width: int):
    """The crop-to-gray chain for one view of one image.

    ``x``: (H, W*3) float32 in [0, 1], channels interleaved along the lanes;
    ``p(k)``: scalar parameter ``k`` of this image's row. Mirrors
    ``simclr_augment_single`` stage for stage and returns (out, out*3).
    Between the resize and the final store, ``r``/``g``/``b`` are
    (out, out*3) planes holding each pixel's channel value on all three of
    its lanes.
    """
    lanes_in, lanes_out = width * 3, out_size * 3
    dst = _float_iota((out_size, height), 0)
    src = _float_iota((out_size, height), 1)
    y = _dot(_bilinear(dst, src, p(0), p(2), out_size, height), x)

    lane_in = _float_iota((lanes_in, lanes_out), 0)
    lane_out = _float_iota((lanes_in, lanes_out), 1)
    col_in = jnp.floor(lane_in / 3.0)
    chan_in = lane_in - 3.0 * col_in
    col_out = jnp.floor(lane_out / 3.0)
    col_out = jnp.where(p(4) > 0.5, (out_size - 1.0) - col_out, col_out)
    w_cols = _bilinear(col_out, col_in, p(1), p(3), out_size, width)
    rgb = tuple(
        _dot(y, jnp.where(chan_in == channel, w_cols, 0.0))
        for channel in (0.0, 1.0, 2.0)
    )

    def brightness(rgb):
        return tuple(jnp.clip(c * p(6), 0.0, 1.0) for c in rgb)

    def contrast(rgb):
        # torchvision blends with the mean of the grayscale image; each
        # pixel's luma sits on three lanes, which leaves the mean unchanged
        luma = _luma(*rgb)
        mean = jnp.sum(
            jnp.sum(luma, axis=1, keepdims=True), axis=0, keepdims=True
        ) / float(out_size * lanes_out)
        return tuple(jnp.clip(mean + p(7) * (c - mean), 0.0, 1.0) for c in rgb)

    def saturation(rgb):
        gray = _luma(*rgb)
        return tuple(jnp.clip(gray + p(8) * (c - gray), 0.0, 1.0) for c in rgb)

    def hue(rgb):
        return _aug._adjust_hue_planes(*rgb, p(9))

    # indexed like augment.color_jitter's ops list
    ops = (brightness, contrast, saturation, hue)

    def jitter(rgb):
        for slot in range(4):
            rgb = jax.lax.switch(p(11 + slot).astype(jnp.int32), ops, rgb)
        return rgb

    def grayscale(rgb):
        gray = _luma(*rgb)
        return gray, gray, gray

    rgb = jax.lax.cond(p(5) > 0.5, jitter, lambda rgb: rgb, rgb)
    r, g, b = jax.lax.cond(p(10) > 0.5, grayscale, lambda rgb: rgb, rgb)
    lane = _float_iota((out_size, lanes_out), 1)
    chan_out = lane - 3.0 * jnp.floor(lane / 3.0)
    return jnp.where(chan_out == 0.0, r, jnp.where(chan_out == 1.0, g, b))


def _augment_kernel(*refs, out_size, height, width, scale, n_views):
    """Grid step: one batch tile. Refs: n_views SMEM param blocks, the image
    block, then n_views output blocks. Each source image is loaded and
    dequantized ONCE (``scale`` = 1/255 for uint8 inputs — this is where
    ``to_float`` happens, in VMEM); every view reads the same registers.
    """
    param_refs = refs[:n_views]
    img_ref = refs[n_views]
    out_refs = refs[n_views + 1:]

    def one_image(t, carry):
        x = img_ref[t]
        if x.dtype == jnp.uint8:
            x = x.astype(jnp.int32)  # Mosaic has no uint8 -> float32 cast
        x = x.astype(jnp.float32) * scale
        for param_ref, out_ref in zip(param_refs, out_refs):
            out_ref[t] = _augment_image(
                x, lambda k, ref=param_ref: ref[t, k], out_size, height, width
            )
        return carry

    jax.lax.fori_loop(0, img_ref.shape[0], one_image, 0)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def _fused_views(images, keys_per_view, strength: float, out_size: int):
    n, height, width, channels = images.shape
    if channels != 3:
        raise ValueError(
            f"fused augmentation expects RGB (N, H, W, 3), got {images.shape}"
        )
    scale = 1.0 / 255.0 if images.dtype == jnp.uint8 else 1.0
    if images.dtype != jnp.uint8:
        images = images.astype(jnp.float32)
    params = [
        _view_params(k, height, width, strength) for k in keys_per_view
    ]
    tn, n_pad = _tile_and_pad(n)
    imgs = _pad_rows(images.reshape(n, height, width * channels), n_pad)
    params = [_pad_rows(p, n_pad) for p in params]
    n_views = len(params)
    kernel = functools.partial(
        _augment_kernel,
        out_size=out_size,
        height=height,
        width=width,
        scale=scale,
        n_views=n_views,
    )
    out_lanes = out_size * channels
    views = pl.pallas_call(
        kernel,
        grid=(n_pad // tn,),
        in_specs=[
            pl.BlockSpec(
                (tn, _N_PARAMS), lambda i: (i, 0), memory_space=pltpu.SMEM
            )
        ]
        * n_views
        + [pl.BlockSpec((tn, height, width * channels), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((tn, out_size, out_lanes), lambda i: (i, 0, 0))]
        * n_views,
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, out_size, out_lanes), jnp.float32)
        ]
        * n_views,
        interpret=_interpret(),
    )(*params, imgs)
    return tuple(
        v[:n].reshape(n, out_size, out_size, channels) for v in views
    )


def fused_two_views(
    rng: jax.Array,
    images: jnp.ndarray,
    strength: float = 0.5,
    out_size: int = 32,
    *,
    keys: jax.Array | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Both SimCLR views of a uint8 (or float) batch in one VMEM pass.

    Key schedule is identical to ``steps._augment_two_views``' XLA path:
    ``split(rng, 2n)``, first half view 0, second half view 1 — so equal
    seeds draw bit-identical augmentation parameters on either impl. The
    training step passes precomputed ``keys`` (same (2n,) layout) so the
    per-sample streams can be derived from GLOBAL batch position instead
    (layout-invariant across elastic remeshes, see
    ``steps._global_sample_keys``); ``rng`` is ignored then.
    """
    n = images.shape[0]
    if keys is None:
        keys = jax.random.split(rng, 2 * n)
    v0, v1 = _fused_views(images, (keys[:n], keys[n:]), strength, out_size)
    return v0, v1


def fused_one_view(
    rng: jax.Array,
    images: jnp.ndarray,
    strength: float = 0.5,
    out_size: int = 32,
    *,
    keys: jax.Array | None = None,
) -> jnp.ndarray:
    """Single augmented view (the supervised baseline's consumption —
    ``split(rng, n)``, same key schedule as its XLA path; ``keys``
    overrides the schedule exactly as in :func:`fused_two_views`)."""
    n = images.shape[0]
    if keys is None:
        keys = jax.random.split(rng, n)
    (view,) = _fused_views(images, (keys,), strength, out_size)
    return view
