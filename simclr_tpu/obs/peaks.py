"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

The one table every utilization figure divides by (the live MFU gauge,
``scripts/roofline_model.py``, ``scripts/perf_attrib.py``). A utilization
against the wrong chip's peak is silently wrong, so there is no default row:
the CPU platform has no peak (no MFU is reported there) and an accelerator
that is not listed is an error. Stdlib-only.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeak:
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    source: str


V5E = "TPU v5 lite"  # jax.devices()[0].device_kind on a v5e chip

CHIP_PEAKS = {
    V5E: ChipPeak(
        bf16_flops_per_s=197e12,
        hbm_bytes_per_s=819e9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


def chip_peak(platform: str, device_kind: str) -> ChipPeak | None:
    """The peaks of one chip of ``device_kind``; ``None`` on the CPU
    platform. Raises ``KeyError`` for an accelerator without a row."""
    if platform == "cpu":
        return None
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} "
            f"(platform {platform!r}); add a row with its source to "
            f"simclr_tpu/obs/peaks.py (known: {sorted(CHIP_PEAKS)})"
        ) from None
