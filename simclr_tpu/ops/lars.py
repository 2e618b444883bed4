"""LARS/LARC optimizer as optax gradient transformations.

The reference wraps SGD-momentum in Apex ``LARC(trust_coefficient=0.001,
clip=False)`` (``/root/reference/main.py:85-94``): per-parameter, an adaptive
factor ``trust * ||p|| / (||g|| + wd * ||p|| + eps)`` multiplies the
weight-decayed gradient, after which plain (non-Nesterov) momentum SGD runs
with its own weight decay disabled. Weight decay is masked off for biases and
batch-norm parameters (``exclude_from_wt_decay``,
``/root/reference/main.py:18-36``); the adaptive scaling itself applies to
*every* parameter, matching Apex LARC (which, unlike google-research LARS,
has no exclude-from-adaptation list).

Reproduced here as an optax chain so it composes with schedules and works
under ``jit``/GSPMD (norms of sharded params become cross-replica reductions
automatically).

Documented deviation: the reference's name-substring skip list ("bias", "bn")
misses torchvision's ``downsample.1`` batch-norms, so those *do* get weight
decay there; our structural mask (every leaf of one axis: biases and norm
scales) excludes all norm parameters uniformly, which is the documented intent.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax

from simclr_tpu.models.arch import CONVS_PER_BLOCK, DOWNSAMPLE_STAGES


def scale_by_larc(
    trust_coefficient: float = 0.001,
    weight_decay: float = 0.0,
    weight_decay_mask: Callable[[Any], Any] | None = None,
    eps: float = 1e-8,
) -> optax.GradientTransformation:
    """Apex-LARC (clip=False) gradient scaling + masked weight decay.

    For each parameter: ``g_out = (g + wd_p * p) * adaptive`` where
    ``adaptive = trust * ||p|| / (||g|| + wd_p * ||p|| + eps)`` if both norms
    are nonzero else 1, and ``wd_p`` is ``weight_decay`` where the mask is
    True else 0. Follow with momentum + lr scaling.
    """

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        if params is None:
            raise ValueError("scale_by_larc requires params")
        if weight_decay_mask is None:
            mask = jax.tree.map(lambda _: True, updates)
        else:
            mask = weight_decay_mask(params)

        def scale(g, p, use_wd):
            g = g.astype(jnp.float32)
            p = p.astype(jnp.float32)
            wd = weight_decay if use_wd else 0.0
            p_norm = jnp.linalg.norm(p)
            g_norm = jnp.linalg.norm(g)
            adaptive = trust_coefficient * p_norm / (g_norm + wd * p_norm + eps)
            # Apex only applies decay+scaling when BOTH norms are nonzero
            # (`if param_norm != 0 and grad_norm != 0`); a zero-grad param
            # must pass through untouched, not decay toward zero.
            active = (p_norm > 0.0) & (g_norm > 0.0)
            return jnp.where(active, (g + wd * p) * adaptive, g)

        updates = jax.tree.map(scale, updates, params, mask)
        return updates, state

    return optax.GradientTransformation(init_fn, update_fn)


def lars(
    learning_rate: float | optax.Schedule,
    trust_coefficient: float = 0.001,
    weight_decay: float = 0.0,
    weight_decay_mask: Callable[[Any], Any] | None = None,
    momentum: float = 0.9,
    eps: float = 1e-8,
) -> optax.GradientTransformation:
    """Full reference optimizer: LARC scaling -> momentum -> -lr.

    ``optax.trace(decay=momentum, nesterov=False)`` reproduces torch SGD's
    momentum buffer (``buf = m * buf + g``, update ``-lr * buf``).
    """
    return optax.chain(
        scale_by_larc(trust_coefficient, weight_decay, weight_decay_mask, eps),
        optax.trace(decay=momentum, nesterov=False),
        optax.scale_by_learning_rate(learning_rate),  # scales by -lr
    )


def reference_weight_decay_mask(params, base_cnn: str = "resnet18") -> Any:
    """The reference's ``("bias", "bn")`` name-substring skip rule
    (``/root/reference/main.py:18-36``) transcribed onto our tree — quirks
    included: torchvision's downsample batch-norm scale (torch name
    ``...downsample.1.weight``) and the projection head's batch-norm scale
    (``g.projection_head.1.weight``) contain neither substring, so the
    reference DOES weight-decay them. Biases never decay (every torch bias
    name contains "bias").

    For training-dynamics parity runs (tests/test_torch_dynamics.py);
    :func:`simclr_weight_decay_mask` remains the default documented intent.
    Select with ``optimizer.weight_decay_mask=reference``.
    """
    downsample_bn = f"BatchNorm_{CONVS_PER_BLOCK[base_cnn]}"
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def decide(path) -> bool:
        names = [str(p.key) for p in path if isinstance(p, jax.tree_util.DictKey)]
        leaf = names[-1]
        if leaf == "bias":
            return False
        if leaf == "scale":
            # f/<Block_i>/BatchNorm_{n_convs} is the projection-shortcut BN
            # (torch downsample.1); g/bn1 is the head BN — both decayed there
            if len(names) >= 3 and names[-2] == downsample_bn and names[0] == "f":
                return True
            return names[0] == "g" and names[-2] == "bn1"
        return True

    decisions = [decide(path) for path, _ in flat]

    # The substring rule keys off Flax auto-index names, so a rename or
    # reordering in resnet.py/heads.py would silently change which scales
    # decay (ADVICE r2). Pin the count structurally: one decayed scale per
    # projection-shortcut stage, plus the head BN iff the tree has one.
    def _leaf(path) -> str:
        return str(
            next(p.key for p in reversed(path) if isinstance(p, jax.tree_util.DictKey))
        )

    decayed_scales = sum(
        1 for (path, _), d in zip(flat, decisions) if d and _leaf(path) == "scale"
    )
    has_head = any(
        str(path[0].key) == "g"
        for path, _ in flat
        if path and isinstance(path[0], jax.tree_util.DictKey)
    )
    expected = DOWNSAMPLE_STAGES[base_cnn] + (1 if has_head else 0)
    if decayed_scales != expected:
        raise ValueError(
            f"reference_weight_decay_mask matched {decayed_scales} decayed norm "
            f"scales but {base_cnn} should have {expected} "
            f"({DOWNSAMPLE_STAGES[base_cnn]} projection-shortcut BNs"
            f"{' + head bn1' if has_head else ''}) — module naming drifted?"
        )
    treedef = jax.tree_util.tree_structure(params)
    return jax.tree_util.tree_unflatten(treedef, decisions)


def get_weight_decay_mask(kind: str, base_cnn: str = "resnet18") -> Callable[[Any], Any]:
    """Mask selection for the ``optimizer.weight_decay_mask`` config key:
    ``structural`` (default, documented intent) or ``reference`` (the torch
    substring rule, quirks included — for exact-recipe parity runs)."""
    if kind == "structural":
        return simclr_weight_decay_mask
    if kind == "reference":
        if base_cnn not in CONVS_PER_BLOCK:
            raise ValueError(
                f"optimizer.weight_decay_mask=reference transcribes the torch "
                f"ResNets' parameter names; {base_cnn!r} has none"
            )
        return lambda params: reference_weight_decay_mask(params, base_cnn)
    raise ValueError(
        f"optimizer.weight_decay_mask must be structural|reference, got {kind!r}"
    )


def simclr_weight_decay_mask(params) -> Any:
    """True where weight decay applies: on every matrix, convolution kernel
    and embedding, and not on biases and norm scales, which are told by what
    a leaf is (one axis or none) and not by what a model's blocks call it:
    the ResNets' ``bias`` and ``scale`` leaves, a sequence encoder's RMS-norm
    scales alike (see module docstring for the deviation from the
    reference's name-substring list).
    """
    return jax.tree.map(lambda p: jnp.ndim(p) > 1, params)
