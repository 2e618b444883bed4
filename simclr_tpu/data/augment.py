"""On-device SimCLR augmentations (pure JAX, jit/vmap-friendly).

The reference runs torchvision CPU transforms in DataLoader worker processes
(``/root/reference/dataset.py:19-38``): RandomResizedCrop(32) -> HFlip(0.5)
-> RandomApply(ColorJitter(0.8s, 0.8s, 0.8s, 0.2s), p=0.8) ->
RandomGrayscale(0.2) -> ToTensor. No Gaussian blur, no mean/std normalize
(correct for CIFAR per the SimCLR paper — SURVEY §2.5.9-10).

TPU-first redesign: augmentation is a jitted, vmapped, per-example-keyed
function that runs ON DEVICE as part of the train step. The host feeds raw
uint8 batches; the two stochastic views are produced by the same XLA program
that consumes them, so there is no per-worker CPU bottleneck and no H2D
traffic beyond the raw images. All shapes are static: the data-dependent
crop/resize is expressed as two (out, in) bilinear sampling matrices applied
as matmuls, with traced crop-box coordinates and coordinates clamped inside
the box (see :func:`random_resized_crop`); the matrices are built elementwise
by comparing a source-index iota with each row's two tap indices, never by a
scatter; and the random-order color jitter uses ``lax.switch`` over op
indices.

Distribution parity with torchvision (the likeliest silent-accuracy-gap
source, SURVEY §7 hard part c):
  * RandomResizedCrop: 10 vectorized attempts of (area scale U(0.08,1),
    log-aspect U(log 3/4, log 4/3)), first in-bounds attempt wins, center-crop
    fallback — same rejection-sampling distribution as torchvision's loop.
  * ColorJitter: brightness/contrast/saturation factors U(max(0,1-0.8s),
    1+0.8s), hue shift U(-0.2s, 0.2s), applied in a uniformly random order of
    the four ops; the whole jitter applied with probability 0.8.
  * Grayscale: ITU-R 601 luma (0.299, 0.587, 0.114), p=0.2.

Images are float32 in [0,1], NHWC.
"""

from __future__ import annotations

import itertools
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# torchvision RandomResizedCrop defaults (scale, ratio) and attempt count.
# Host-side constants (numpy/math, not jnp) so importing this module never
# initializes a JAX backend.
_CROP_SCALE = (0.08, 1.0)
_CROP_LOG_RATIO = (math.log(3.0 / 4.0), math.log(4.0 / 3.0))
_CROP_ATTEMPTS = 10

_GRAY_WEIGHTS = np.array([0.299, 0.587, 0.114], dtype=np.float32)


def to_float(image: jnp.ndarray) -> jnp.ndarray:
    """uint8 [0,255] -> float32 [0,1] (torchvision ToTensor semantics)."""
    if image.dtype == jnp.uint8:
        return image.astype(jnp.float32) / 255.0
    return image.astype(jnp.float32)


# ---------------------------------------------------------------------------
# RandomResizedCrop
# ---------------------------------------------------------------------------

def _sample_crop_box(key: jax.Array, height: int, width: int):
    """Sample (top, left, h, w) floats per torchvision RandomResizedCrop.

    Vectorized form of the reference transform's 10-attempt rejection loop:
    all attempts are sampled at once, the first in-bounds one is selected,
    and the torchvision center-crop fallback (aspect clamped to the ratio
    range) is used when every attempt misses.
    """
    k_area, k_ratio, k_top, k_left = jax.random.split(key, 4)
    area = float(height * width)

    target_area = area * jax.random.uniform(
        k_area, (_CROP_ATTEMPTS,), minval=_CROP_SCALE[0], maxval=_CROP_SCALE[1]
    )
    aspect = jnp.exp(
        jax.random.uniform(
            k_ratio,
            (_CROP_ATTEMPTS,),
            minval=_CROP_LOG_RATIO[0],
            maxval=_CROP_LOG_RATIO[1],
        )
    )
    # torchvision rounds w/h to ints before the bounds check
    w = jnp.round(jnp.sqrt(target_area * aspect))
    h = jnp.round(jnp.sqrt(target_area / aspect))
    valid = (w > 0) & (w <= width) & (h > 0) & (h <= height)
    # first valid attempt (argmax returns the first True)
    pick = jnp.argmax(valid)
    any_valid = jnp.any(valid)

    w_pick = w[pick]
    h_pick = h[pick]
    # uniform placement: torchvision samples integer top/left in
    # [0, H-h] x [0, W-w] inclusive
    u_top = jax.random.uniform(k_top)
    u_left = jax.random.uniform(k_left)
    top = jnp.floor(u_top * (height - h_pick + 1.0))
    left = jnp.floor(u_left * (width - w_pick + 1.0))

    # fallback: central crop with aspect clamped into the ratio range
    in_ratio = width / height
    fb_w = jnp.where(
        in_ratio < jnp.exp(_CROP_LOG_RATIO[0]),
        float(width),
        jnp.where(
            in_ratio > jnp.exp(_CROP_LOG_RATIO[1]),
            jnp.round(height * jnp.exp(_CROP_LOG_RATIO[1])),
            float(width),
        ),
    )
    fb_h = jnp.where(
        in_ratio < jnp.exp(_CROP_LOG_RATIO[0]),
        jnp.round(width / jnp.exp(_CROP_LOG_RATIO[0])),
        jnp.where(in_ratio > jnp.exp(_CROP_LOG_RATIO[1]), float(height), float(height)),
    )
    fb_top = jnp.round((height - fb_h) / 2.0)
    fb_left = jnp.round((width - fb_w) / 2.0)

    top = jnp.where(any_valid, top, fb_top)
    left = jnp.where(any_valid, left, fb_left)
    h_out = jnp.where(any_valid, h_pick, fb_h)
    w_out = jnp.where(any_valid, w_pick, fb_w)
    return top, left, h_out, w_out


def _axis_resize_weights(
    origin: jnp.ndarray, size: jnp.ndarray, out_size: int, in_size: int
) -> jnp.ndarray:
    """(out_size, in_size) bilinear sampling matrix for one axis.

    Sample centers follow the half-pixel convention torch/PIL use
    (``src = origin + (dst + 0.5) * size/out - 0.5``) and are CLAMPED to the
    crop box, so border pixels replicate the box edge exactly as a
    crop-then-resize does — never bleeding into source pixels outside the
    sampled box.

    Each row holds its two taps, ``1 - frac`` at ``i0`` and ``frac`` at
    ``i1``, selected elementwise by comparing a source-index iota with the
    tap indices and added in that order, so a clipped border where
    ``i0 == i1`` sums both into one column. No scatter: under ``vmap`` a
    scatter-add of the taps is one serial update per tap on the TPU (PERF.md,
    Findings, PR 32); the comparison is one elementwise fusion.
    """
    centers = origin + (jnp.arange(out_size, dtype=jnp.float32) + 0.5) * (
        size / out_size
    ) - 0.5
    centers = jnp.clip(centers, origin, origin + size - 1.0)
    i0 = jnp.floor(centers)
    frac = centers - i0
    i0 = jnp.clip(i0.astype(jnp.int32), 0, in_size - 1)
    i1 = jnp.clip(i0 + 1, 0, in_size - 1)
    src = jnp.arange(in_size)
    return jnp.where(src == i0[:, None], (1.0 - frac)[:, None], 0.0) + jnp.where(
        src == i1[:, None], frac[:, None], 0.0
    )


def random_resized_crop(
    key: jax.Array, image: jnp.ndarray, out_size: int = 32
) -> jnp.ndarray:
    """Crop a random box and resize to (out_size, out_size) bilinearly.

    The dynamic-size crop + static-size resize is expressed as two static
    (out, H)/(out, W) sampling matrices applied as matmuls (MXU-friendly, no
    dynamic shapes), with sample coordinates clamped inside the crop box —
    matching crop-then-resize edge behavior. Remaining documented deviation
    from torchvision: PIL antialiases when downscaling; this is plain
    bilinear.
    """
    height, width = image.shape[0], image.shape[1]
    top, left, crop_h, crop_w = _sample_crop_box(key, height, width)

    w_rows = _axis_resize_weights(top, crop_h, out_size, height)      # (out, H)
    w_cols = _axis_resize_weights(left, crop_w, out_size, width)      # (out, W)
    img = image.astype(jnp.float32)
    return jnp.einsum("oh,hwc,pw->opc", w_rows, img, w_cols)


# ---------------------------------------------------------------------------
# Color ops (torchvision functional semantics on [0,1] floats)
# ---------------------------------------------------------------------------

def _grayscale(image: jnp.ndarray) -> jnp.ndarray:
    luma = jnp.tensordot(image, _GRAY_WEIGHTS, axes=[[-1], [0]])
    return luma[..., None] * jnp.ones((1, 1, image.shape[-1]), image.dtype)


def adjust_brightness(image: jnp.ndarray, factor: jnp.ndarray) -> jnp.ndarray:
    return jnp.clip(image * factor, 0.0, 1.0)


def adjust_contrast(image: jnp.ndarray, factor: jnp.ndarray) -> jnp.ndarray:
    # torchvision blends with the MEAN OF THE GRAYSCALE image
    mean = _grayscale(image).mean()
    return jnp.clip(mean + factor * (image - mean), 0.0, 1.0)


def adjust_saturation(image: jnp.ndarray, factor: jnp.ndarray) -> jnp.ndarray:
    gray = _grayscale(image)
    return jnp.clip(gray + factor * (image - gray), 0.0, 1.0)


def _adjust_hue_planes(r, g, b, delta):
    """:func:`adjust_hue` on separate, same-shaped channel planes; returns
    the shifted ``(r, g, b)``. The fused kernel (``ops/augment_pallas.py``)
    calls this on lane-dense planes, so the two paths share one formula."""
    maxc = jnp.maximum(jnp.maximum(r, g), b)
    minc = jnp.minimum(jnp.minimum(r, g), b)
    value = maxc
    chroma = maxc - minc
    safe_chroma = jnp.where(chroma > 0, chroma, 1.0)
    sat = jnp.where(maxc > 0, chroma / jnp.where(maxc > 0, maxc, 1.0), 0.0)

    hue = jnp.where(
        maxc == r,
        ((g - b) / safe_chroma) % 6.0,
        jnp.where(maxc == g, (b - r) / safe_chroma + 2.0, (r - g) / safe_chroma + 4.0),
    )
    hue = jnp.where(chroma > 0, hue / 6.0, 0.0)
    hue = (hue + delta) % 1.0

    # HSV -> RGB
    h6 = hue * 6.0
    i = jnp.floor(h6)
    f = h6 - i
    p = value * (1.0 - sat)
    q = value * (1.0 - sat * f)
    t = value * (1.0 - sat * (1.0 - f))
    i = i.astype(jnp.int32) % 6

    def by_sector(*choices):
        # a where-chain, not jnp.select: select lowers through an integer
        # argmax, which Mosaic does not implement
        out = choices[5]
        for sector in (4, 3, 2, 1, 0):
            out = jnp.where(i == sector, choices[sector], out)
        return out

    r_out = by_sector(value, q, p, p, t, value)
    g_out = by_sector(t, value, value, q, p, p)
    b_out = by_sector(p, p, t, value, value, q)
    return tuple(jnp.clip(c, 0.0, 1.0) for c in (r_out, g_out, b_out))


def adjust_hue(image: jnp.ndarray, delta: jnp.ndarray) -> jnp.ndarray:
    """Shift hue by ``delta`` (in turns, torchvision range [-0.5, 0.5])."""
    return jnp.stack(
        _adjust_hue_planes(image[..., 0], image[..., 1], image[..., 2], delta),
        axis=-1,
    )


_JITTER_PERMS = np.array(list(itertools.permutations(range(4))), dtype=np.int32)


def jitter_params(key: jax.Array, strength: float = 0.5):
    """Sample ColorJitter(0.8s, 0.8s, 0.8s, 0.2s) parameters: the three
    blend factors U(max(0,1-r), 1+r), the hue shift U(-h, h), and the op
    permutation index (uniform over all 24 orders). Factored out of
    :func:`color_jitter` so distribution tests measure the SAME sampler the
    pipeline runs (tests/test_augment_distribution.py)."""
    b, c, s, h = 0.8 * strength, 0.8 * strength, 0.8 * strength, 0.2 * strength
    k_b, k_c, k_s, k_h, k_perm = jax.random.split(key, 5)
    f_b = jax.random.uniform(k_b, minval=max(0.0, 1.0 - b), maxval=1.0 + b)
    f_c = jax.random.uniform(k_c, minval=max(0.0, 1.0 - c), maxval=1.0 + c)
    f_s = jax.random.uniform(k_s, minval=max(0.0, 1.0 - s), maxval=1.0 + s)
    f_h = jax.random.uniform(k_h, minval=-h, maxval=h)
    perm_idx = jax.random.randint(k_perm, (), 0, _JITTER_PERMS.shape[0])
    return f_b, f_c, f_s, f_h, perm_idx


def color_jitter(
    key: jax.Array, image: jnp.ndarray, strength: float = 0.5
) -> jnp.ndarray:
    """torchvision ColorJitter(0.8s, 0.8s, 0.8s, 0.2s) with random op order."""
    f_b, f_c, f_s, f_h, perm_idx = jitter_params(key, strength)

    ops = [
        lambda img: adjust_brightness(img, f_b),
        lambda img: adjust_contrast(img, f_c),
        lambda img: adjust_saturation(img, f_s),
        lambda img: adjust_hue(img, f_h),
    ]
    perm = jnp.asarray(_JITTER_PERMS)[perm_idx]
    for slot in range(4):
        image = lax.switch(perm[slot], ops, image)
    return image


def random_grayscale(key: jax.Array, image: jnp.ndarray, p: float = 0.2) -> jnp.ndarray:
    apply = jax.random.uniform(key) < p
    return jnp.where(apply, _grayscale(image), image)


def random_hflip(key: jax.Array, image: jnp.ndarray, p: float = 0.5) -> jnp.ndarray:
    apply = jax.random.uniform(key) < p
    return jnp.where(apply, image[:, ::-1, :], image)


# ---------------------------------------------------------------------------
# Full pipelines
# ---------------------------------------------------------------------------

# reference pipeline probabilities (dataset.py:27-35): RandomApply(jitter)
# p=0.8, RandomGrayscale 0.2, RandomHorizontalFlip 0.5
_JITTER_APPLY_P = 0.8
_GRAYSCALE_P = 0.2
_HFLIP_P = 0.5


def _view_keys(key: jax.Array):
    """The one per-view key split (crop, flip, jitter-gate, jitter, gray) —
    shared with tests that reconstruct individual pipeline branches."""
    return jax.random.split(key, 5)


def simclr_augment_single(
    key: jax.Array,
    image: jnp.ndarray,
    strength: float = 0.5,
    out_size: int = 32,
) -> jnp.ndarray:
    """One stochastic SimCLR view of one image (HWC float32 in [0, 1]).

    Callers convert uint8 once per IMAGE via :func:`to_float` before
    vmapping this over views (it used to live here, paying the dequant once
    per view); the fused Pallas kernel (``ops/augment_pallas.py``)
    dequantizes in-VMEM instead and never materializes the float image in
    HBM at all.
    """
    image = image.astype(jnp.float32)
    k_crop, k_flip, k_apply, k_jitter, k_gray = _view_keys(key)
    image = random_resized_crop(k_crop, image, out_size=out_size)
    image = random_hflip(k_flip, image, p=_HFLIP_P)
    jittered = color_jitter(k_jitter, image, strength=strength)
    image = jnp.where(jax.random.uniform(k_apply) < _JITTER_APPLY_P, jittered, image)
    image = random_grayscale(k_gray, image, p=_GRAYSCALE_P)
    return image


@partial(jax.jit, static_argnames=("strength", "out_size"))
def simclr_two_views(
    key: jax.Array,
    images: jnp.ndarray,
    strength: float = 0.5,
    out_size: int = 32,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Two independent augmented views of a batch (N,H,W,C).

    Mirrors ``SimCLRTransforms.__call__`` returning two independent draws
    (``/root/reference/dataset.py:49-50``), vectorized over the batch with
    per-example PRNG keys. uint8 input converts to float ONCE here (not
    once per view — :func:`simclr_augment_single` takes floats).
    """
    images = to_float(images)
    n = images.shape[0]
    keys = jax.random.split(key, 2 * n)
    aug = jax.vmap(simclr_augment_single, in_axes=(0, 0, None, None))
    view0 = aug(keys[:n], images, strength, out_size)
    view1 = aug(keys[n:], images, strength, out_size)
    return view0, view1
