"""Top-level model compositions: contrastive (encoder+head) and supervised.

Capability parity with ``/root/reference/model.py:76-168``:
  * :class:`ContrastiveModel` — encoder ``f`` + projection head ``g``;
    ``encode()`` returns pre-head features h (``model.py:116-123``),
    ``__call__`` returns projected z (``model.py:125-129``).
  * :class:`SupervisedModel` — encoder ``f`` + linear ``fc``
    (``model.py:132-168``).

Both expose ``train`` flags threading through BatchNorm; under a GSPMD ``jit``
with the batch sharded over the data mesh axis, BN statistics are global-batch
statistics (= reference SyncBN over the whole world).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from simclr_tpu.models.arch import SEQUENCE_ENCODERS, STAGE_SIZES
from simclr_tpu.models.heads import ProjectionHead
from simclr_tpu.models.resnet import ResNetEncoder
from simclr_tpu.models.sequence import SequenceEncoder
from simclr_tpu.obs.trace import SCOPE_HEAD

Dtype = Any


def build_encoder(
    name: str, *, dtype: Dtype, cifar_stem: bool = True,
    bn_cross_replica_axis: str | None = None,
) -> nn.Module:
    """The encoder ``name`` of the one table (``models/arch.py``): a ResNet
    over images, or a sequence encoder over packed token rows
    (``models/sequence.PackedView``). Either gives one float32 feature row
    per example, ``FEATURE_DIMS[name]`` wide."""
    if name in SEQUENCE_ENCODERS:
        return SequenceEncoder(spec=SEQUENCE_ENCODERS[name], dtype=dtype)
    if name in STAGE_SIZES:
        return ResNetEncoder(
            base_cnn=name, cifar_stem=cifar_stem, dtype=dtype,
            bn_cross_replica_axis=bn_cross_replica_axis,
        )
    raise ValueError(
        f"no encoder {name!r}; have {sorted((*STAGE_SIZES, *SEQUENCE_ENCODERS))}"
    )


class ContrastiveModel(nn.Module):
    """SimCLR model: z = g(f(x)). ``encode`` gives h = f(x). ``base_cnn``
    names the encoder, a ResNet or a sequence encoder (:func:`build_encoder`;
    the field keeps the name every recipe and checkpoint knows)."""

    base_cnn: str = "resnet18"
    d: int = 128
    cifar_stem: bool = True
    dtype: Dtype = jnp.bfloat16
    bn_cross_replica_axis: str | None = None
    # tensor parallelism of the projection head (parallel/tp.py): the LOCAL
    # per-shard hidden width and the mesh axis the head is sharded over.
    # Defaults give the global (unsharded) view used for init/checkpoints.
    head_hidden: int | None = None
    head_tp_axis: str | None = None

    def setup(self):
        self.f = build_encoder(
            self.base_cnn,
            cifar_stem=self.cifar_stem,
            dtype=self.dtype,
            bn_cross_replica_axis=self.bn_cross_replica_axis,
        )
        self.g = ProjectionHead(
            d=self.d,
            dtype=self.dtype,
            axis_name=self.bn_cross_replica_axis,
            hidden=self.head_hidden,
            tp_axis=self.head_tp_axis,
        )

    def encode(self, x, train: bool = True):
        return self.f(x, train=train)

    def project(self, h, train: bool = True):
        return self.g(h, train=train)

    def __call__(self, x, train: bool = True):
        h = self.encode(x, train=train)
        # the projection head's share of the step's `encoder` scope
        with jax.named_scope(SCOPE_HEAD):
            return self.g(h, train=train)


class SupervisedModel(nn.Module):
    """Encoder + linear classification layer (supervised baseline)."""

    base_cnn: str = "resnet18"
    num_classes: int = 10
    cifar_stem: bool = True
    dtype: Dtype = jnp.bfloat16
    bn_cross_replica_axis: str | None = None

    def setup(self):
        self.f = ResNetEncoder(
            base_cnn=self.base_cnn,
            cifar_stem=self.cifar_stem,
            dtype=self.dtype,
            bn_cross_replica_axis=self.bn_cross_replica_axis,
        )
        self.fc = nn.Dense(self.num_classes, dtype=jnp.float32, param_dtype=jnp.float32)

    def encode(self, x, train: bool = True):
        return self.f(x, train=train)

    def __call__(self, x, train: bool = True):
        return self.fc(self.f(x, train=train))
