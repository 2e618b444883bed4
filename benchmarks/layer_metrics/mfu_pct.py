"""Model FLOP/s utilisation: images per second and chip, times the cell's
``flops_per_image`` (data of the cell, fixed when it was defined, so that
recomputation cannot raise it), over the chip's bf16 peak."""

import json
import os

from benchmarks.end_to_end import imgs_per_s_per_chip

PEAKS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "peaks.json")


def read(run: dict) -> float | None:
    rate = imgs_per_s_per_chip.read(run)
    if rate is None or run["device"]["platform"] == "cpu":
        return None
    with open(PEAKS) as f:
        peaks = json.load(f)["chips"]
    kind = run["device"]["kind"]
    if kind not in peaks:
        raise KeyError(
            f"no published peak for device_kind {kind!r} in benchmarks/peaks.json"
        )
    flops = float(run["cell"]["data"]["flops_per_image"])
    return 100.0 * rate * flops / float(peaks[kind]["bf16_flops_per_s"])
