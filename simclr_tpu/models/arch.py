"""Encoder architecture tables — the single source of truth.

Consumed by the Flax encoder (``models/resnet.py``), the torch checkpoint
importer (``utils/torch_import.py``), and the reference-exact weight-decay
mask (``ops/lars.py``), so adding an architecture or changing a depth is a
one-file edit.

The reference zoo is {resnet18, resnet50} (``/root/reference/model.py:87``);
resnet34 (BasicBlock at resnet50's stage depths) and resnet101 (Bottleneck,
23-block stage 3) are additions.
"""

from __future__ import annotations

import dataclasses

STAGE_SIZES: dict[str, tuple[int, int, int, int]] = {
    "resnet18": (2, 2, 2, 2),
    "resnet34": (3, 4, 6, 3),
    "resnet50": (3, 4, 6, 3),
    "resnet101": (3, 4, 23, 3),
}
STAGE_WIDTHS: tuple[int, int, int, int] = (64, 128, 256, 512)
BASIC_BLOCK_CNNS: tuple[str, ...] = ("resnet18", "resnet34")
# convs per residual block: 2 for BasicBlock, 3 for Bottleneck — also the
# Flax auto-index of the projection-shortcut BatchNorm (torch downsample.1)
CONVS_PER_BLOCK: dict[str, int] = {
    name: (2 if name in BASIC_BLOCK_CNNS else 3) for name in STAGE_SIZES
}
BLOCK_NAME: dict[str, str] = {
    name: ("BasicBlock" if name in BASIC_BLOCK_CNNS else "BottleneckBlock")
    for name in STAGE_SIZES
}
FEATURE_DIMS: dict[str, int] = {
    name: (STAGE_WIDTHS[-1] if name in BASIC_BLOCK_CNNS else STAGE_WIDTHS[-1] * 4)
    for name in STAGE_SIZES
}
# stages whose first block carries a projection shortcut (torch `downsample`):
# stages 2-4 always stride; stage 1 only widens channels for Bottleneck (the
# CIFAR stem outputs 64 = BasicBlock stage-1 width, but Bottleneck expands ×4)
DOWNSAMPLE_STAGES: dict[str, int] = {
    name: (3 if name in BASIC_BLOCK_CNNS else 4) for name in STAGE_SIZES
}


# -- sequence encoders ---------------------------------------------------------
# The other half of the one table ``experiment.base_cnn`` names an encoder
# from (the key keeps its name: every ResNet recipe stays valid). A sequence
# encoder takes packed rows of token ids with segment ids and positions
# (``data/documents.py``) and gives one feature row per document.


@dataclasses.dataclass(frozen=True)
class SequenceEncoderSpec:
    """Shapes of a decoder-style token encoder with routed experts
    (``models/sequence.py``). ``layout[i]`` is 0 for a layer of full causal
    attention without rotary embedding, 1 for causal attention over the last
    ``window`` positions with it. The router always has ``experts`` outputs
    and picks ``experts_per_token``; this chip holds ``experts_held`` of them
    from ``first_expert`` on, and ``vocab_held`` rows of the embedding."""

    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    layout: tuple[int, ...]
    window: int
    rope_theta: float
    rms_eps: float
    experts: int
    experts_per_token: int
    expert_width: int
    experts_held: int
    vocab_held: int
    first_expert: int = 0


SEQUENCE_ENCODERS: dict[str, SequenceEncoderSpec] = {
    # PowerInfer/SmallThinker-21BA3B-Instruct config.json at published widths;
    # one period of its 52 layers, 16 of its 64 experts and a quarter of its
    # 151,936 vocabulary rows: one chip's share of a layer divided over four
    # (benchmarks/configs/smallthinker-21ba3b-enc.json has the arithmetic)
    "smallthinker-21ba3b-enc": SequenceEncoderSpec(
        hidden=2560, heads=28, kv_heads=4, head_dim=128, layout=(0, 1, 1, 1),
        window=4096, rope_theta=1.5e6, rms_eps=1e-6, experts=64,
        experts_per_token=6, expert_width=768, experts_held=16,
        vocab_held=37984,
    ),
    # the same block at toy widths, for the CPU tests and rehearsals
    "smallthinker-tiny": SequenceEncoderSpec(
        hidden=64, heads=4, kv_heads=2, head_dim=16, layout=(0, 1, 1, 1),
        window=8, rope_theta=1.5e6, rms_eps=1e-6, experts=8,
        experts_per_token=2, expert_width=32, experts_held=4, vocab_held=512,
    ),
}
ENCODERS: tuple[str, ...] = tuple(sorted((*STAGE_SIZES, *SEQUENCE_ENCODERS)))
FEATURE_DIMS.update({name: s.hidden for name, s in SEQUENCE_ENCODERS.items()})


def refuse_sequence_encoder(what, surface: str) -> None:
    """Raise where an image surface (probes, feature export, the serving
    tier) is handed a sequence encoder: ``what`` is an encoder's name or a
    checkpoint's parameter tree. Their inputs are images and their requests
    pixel arrays; a token encoder's checkpoint has to be refused by name, not
    reshaped."""
    if isinstance(what, str):
        is_sequence = what in SEQUENCE_ENCODERS
    else:
        is_sequence = "embedding" in dict(what).get("f", {})
    if is_sequence:
        raise ValueError(
            f"{surface} takes images, and this is a sequence encoder "
            f"({sorted(SEQUENCE_ENCODERS)}: packed rows of token ids, "
            "models/sequence.py): token inputs are not built for it "
            "(ROADMAP.md, Reach)"
        )
