"""The work of a sequence encoder's two kernels' scopes in one training step,
counted from shapes alone: for the roofline shares ``attention_roofline_pct``
and ``experts_roofline_pct`` (``layer_metrics/``).

What is counted is what the mathematics needs, whatever implements it:
forward once and backward once (twice the forward's products), nothing for
rematerialisation, and of the attention scores only the (query, key) pairs
the masks allow, so that a kernel which also computes masked-out blocks, or a
step that recomputes a layer, reads a smaller share and none can pass 100%.
Inputs are the configuration's file (the model's own ``config.json`` keys),
the mix's ``crop_lengths`` and ``rows_per_step``, and for the experts the
program's counter of (token, expert) pairs computed on held experts.

Bytes are the least traffic with HBM: each parameter of the scope read in
float32 by the forward and by the backward pass and its gradient written
(12 bytes), and the scope's activations in and out at the precision the
configuration states (float32 residual stream, bfloat16 product inputs).
Both scopes are compute-bound at these shapes, so the bytes decide nothing
here; they are counted so that the same functions hold for a cell where
they do.
"""

from __future__ import annotations

PASSES = 3  # forward, and a backward of twice its products
F32, BF16 = 4, 2


def allowed_pairs(length: int, window: int | None) -> int:
    """(query, key) pairs of one document under the causal mask, cut to the
    last ``window`` positions (the query's own included) if given."""
    if window is None or length <= window:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def _layers(config: dict) -> list[int]:
    return list(config["rope_layout"][: int(config["num_hidden_layers"])])


def attention_work(config: dict, crop_lengths, rows_per_step: int) -> dict:
    """FLOPs and bytes of the ``attention`` scope in one step: the norm's
    output through q, k, v, the scores, and the output projection, every
    layer, ``rows_per_step`` rows of ``crop_lengths`` (both views)."""
    hidden, d = int(config["hidden_size"]), int(config["head_dim"])
    heads, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    tokens = rows_per_step * sum(crop_lengths)
    projected = 2 * hidden * d * (2 * heads + 2 * kv)  # q, k, v and o, per token
    flops = bytes_ = 0
    for kind in _layers(config):
        window = int(config["sliding_window_size"]) if kind else None
        pairs = rows_per_step * sum(allowed_pairs(n, window) for n in crop_lengths)
        flops += PASSES * (tokens * projected + pairs * heads * 4 * d)
        parameters = hidden * d * (2 * heads + 2 * kv) + hidden
        # in: the residual stream; between: q, k, v and the heads' output,
        # written and read once; out: the residual stream
        activations = tokens * (2 * F32 * hidden + 2 * BF16 * d * (2 * heads + 2 * kv))
        bytes_ += 3 * F32 * parameters + PASSES * activations
    return {"flops": float(flops), "bytes": float(bytes_)}


def experts_work(config: dict, held_pairs: float) -> dict:
    """FLOPs and bytes of the ``experts`` scope in one step: the three
    products of every (token, expert) pair computed on a held expert, all
    layers together (``held_pairs`` is the program's counter), and the held
    experts' parameters."""
    hidden, width = int(config["hidden_size"]), int(config["moe_ffn_hidden_size"])
    held, layers = int(config["moe_num_primary_experts"]), len(_layers(config))
    flops = PASSES * held_pairs * 3 * 2 * hidden * width
    parameters = layers * (held * 3 * hidden * width + hidden)
    # a pair's row in and out at the model's width, its hidden row between
    activations = held_pairs * BF16 * (2 * hidden + 4 * width)
    return {"flops": float(flops), "bytes": float(3 * F32 * parameters + PASSES * activations)}


def roofline_ms(work: dict, peak: dict) -> float:
    """The least milliseconds one chip needs for ``work``: the larger of its
    FLOPs over the bf16 peak and its bytes over the HBM peak."""
    return 1e3 * max(
        work["flops"] / float(peak["bf16_flops_per_s"]),
        work["bytes"] / float(peak["hbm_bytes_per_s"]),
    )


def peak_of(run: dict) -> dict | None:
    """The run's chip in ``peaks.json``; ``None`` on the CPU, an error for a
    device that is not listed."""
    import json
    import os

    if run["device"]["platform"] == "cpu":
        return None
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        peaks = json.load(f)["chips"]
    kind = run["device"]["kind"]
    if kind not in peaks:
        raise KeyError(f"no published peak for device_kind {kind!r} in benchmarks/peaks.json")
    return peaks[kind]


def counter(run: dict, name: str) -> float | None:
    """Mean of the ``epoch`` events' field ``name`` over the window's events
    (each is one step's reading, fetched with the epoch's loss); ``None``
    where the program wrote none."""
    from benchmarks import reduce

    values = [e[name] for e in reduce.window_epochs(run) if e.get(name) is not None]
    return sum(values) / len(values) if values else None
