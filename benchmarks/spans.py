"""The trainer's host spans (``<run_dir>/spans.jsonl``), read for the benchmark.

The trainer records what its host loop does as spans ``name, t0, t1`` in
``time.monotonic_ns()`` with the ``parent`` that was open on the same thread,
the ``thread``, and the ``step`` and ``epoch`` a span serves, keeps them in
memory and appends them to the file at flush points (after each checkpoint,
and when the run ends, however it ends). Every flush starts with a header
line that holds ``wall_ns`` and ``mono_ns`` read back to back.

Two clocks, and no third. The conductor's ``t_start``, ``seen``,
``t_first_beat``, ``t_sigterm`` and ``t_exit`` (``runners/train.py``) are
``time.monotonic()`` of the same process, so a span's seconds here are on
the run record's clock with no conversion. A device trace counts
nanoseconds from its ``profile_start_time`` (Unix ns, ``xplane.read``'s
``start_wall_ns``); the header's ``wall_ns - mono_ns`` maps a span onto it.

A span that was open at one flush and closed by the next is in the file
twice under one ``id``; the later line wins. A run directory without the
file (a program from before the recorder) reads as no spans, and every
reader below then returns ``None``.

    python3 benchmarks/spans.py <run_dir>

prints the boot spans, the boundaries' children by self time (medians over
the run's boundaries) and the parts of the preemption, for a reader of a run
directory outside the benchmark.
"""

from __future__ import annotations

import json
import math
import os
import statistics
import sys

if __name__ == "__main__":  # run as a script: the package from the checkout
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import reduce, xplane

SPANS_NAME = "spans.jsonl"
MAIN_THREAD = "MainThread"  # the runner calls the trainer on the main thread
# the spans whose host work leaves the device idle without the step loop's
# doing (idle_in_steps_pct)
NOT_THE_STEP_LOOP = ("boundary", "checkpoint", "preempt")


def read_file(path: str) -> tuple[list[dict], list[dict]]:
    """``(headers, spans)`` of a spans file, a span under its last line;
    times in seconds on the monotonic clock. ``([], [])`` without the file."""
    headers, latest = [], {}
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError:
        return [], []
    pid = None
    for line in lines:
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if not isinstance(row, dict):
            continue
        if "header" in row:
            headers.append(row)
            pid = row.get("pid")
        elif "id" in row and "t0" in row and "t1" in row:
            # ids count up within one process; a resumed run appends another's
            latest[(pid, row["id"])] = {
                **row, "pid": pid, "t0": row["t0"] / 1e9, "t1": row["t1"] / 1e9,
            }
    return headers, sorted(latest.values(), key=lambda s: s["t0"])


def of_run(run: dict) -> dict | None:
    """``{"headers", "spans"}`` of the run's process 0, read once per run
    record; ``None`` where the trainer wrote none."""
    if "spans" not in run:
        headers, spans = read_file(os.path.join(run.get("run_dir") or "", SPANS_NAME))
        run["spans"] = {"headers": headers, "spans": spans} if spans else None
    return run["spans"]


def named(spans: list[dict], name: str) -> list[dict]:
    return [s for s in spans if s["name"] == name and not s.get("open")]


def children(spans: list[dict], parent: dict) -> list[dict]:
    return [
        s for s in spans
        if s.get("parent") == parent["id"] and s["pid"] == parent["pid"]
    ]


def self_time(spans: list[dict], span: dict) -> float:
    """A span's seconds less the part its children cover."""
    kids = [(c["t0"] * 1e9, (c["t1"] - c["t0"]) * 1e9, "") for c in children(spans, span)]
    covered = sum(e - s for s, e in xplane.merged(kids)) / 1e9
    return (span["t1"] - span["t0"]) - covered


def by_id(spans: list[dict]) -> dict:
    return {(s["pid"], s["id"]): s for s in spans}


def has_ancestor(index: dict, span: dict, name: str) -> bool:
    """Whether a span of that name is above ``span``; ``index`` is
    ``by_id(spans)``."""
    for _ in range(16):
        span = index.get((span["pid"], span.get("parent")))
        if span is None:
            return False
        if span["name"] == name:
            return True
    return False


def last(run: dict, name: str, under: str | None = None) -> dict | None:
    """The run's last closed span of that name (under an ancestor of the
    name ``under``, if given); ``None`` if there is none."""
    data = of_run(run)
    if data is None:
        return None
    index = by_id(data["spans"]) if under else {}
    found = [
        s for s in named(data["spans"], name)
        if under is None or has_ancestor(index, s, under)
    ]
    return found[-1] if found else None


def duration(run: dict, name: str, under: str | None = None) -> float | None:
    span = last(run, name, under)
    return None if span is None else span["t1"] - span["t0"]


# -- the measured window ------------------------------------------------------
def measured_intervals(run: dict) -> list[tuple[float, float]]:
    """The stretches ``reduce.measured`` counts: from one ``epoch`` event of
    the window to the next, those the profiler touched left out."""
    epochs = reduce.window_epochs(run)
    c0, c1 = run.get("capture") or (math.inf, -math.inf)
    return [
        (a["seen"], b["seen"]) for a, b in zip(epochs, epochs[1:])
        if b["seen"] <= c0 or a["seen"] >= c1
    ]


def inside(t: float, intervals: list[tuple[float, float]]) -> bool:
    return any(lo <= t < hi for lo, hi in intervals)


def input_wait_ms(run: dict) -> float | None:
    """Milliseconds per step the main thread waited in ``next()`` on the
    prefetch queue, over the measured epochs; the wait for an epoch's first
    batch belongs to the boundary (``boundary/first_batch``) and is left out."""
    data, m = of_run(run), reduce.measured(run)
    if data is None or m is None:
        return None
    spans, intervals = data["spans"], measured_intervals(run)
    first = {(s["pid"], s["id"]) for s in named(spans, "boundary/first_batch")}
    waits = [
        s for s in named(spans, "input_wait")
        if (s["pid"], s.get("parent")) not in first and inside(s["t0"], intervals)
    ]
    if not waits:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in waits) / m["steps"]


def boundary_host_ms(run: dict) -> float | None:
    """Mean milliseconds, over the boundaries whose host work begins inside
    the measured epochs, from the end of ``boundary/loss_fetch`` to the end
    of ``boundary``: the host work (telemetry, heartbeat, log line, event,
    results file, shuffle, the first batch's gather and copy) during which
    the device has nothing to run. The boundary before the window's first
    ``epoch`` event is left out with its epoch: it holds the first-time
    compilations of the small programs a boundary runs (0.55 s on the chip)."""
    data = of_run(run)
    if data is None:
        return None
    spans, intervals = data["spans"], measured_intervals(run)
    values = []
    for boundary in named(spans, "boundary"):
        fetches = [c for c in children(spans, boundary) if c["name"] == "boundary/loss_fetch"]
        if fetches and inside(fetches[-1]["t1"], intervals):
            values.append(boundary["t1"] - fetches[-1]["t1"])
    return 1e3 * sum(values) / len(values) if values else None


# -- beside the device trace ---------------------------------------------------
def to_trace_ns(run: dict) -> float | None:
    """What to add to a span's seconds x 1e9 for nanoseconds on the trace's
    clock; ``None`` without a header or without the trace's start time."""
    data, trace = of_run(run), run.get("trace")
    if data is None or not data["headers"] or not trace:
        return None
    if trace.get("start_wall_ns") is None:
        return None
    anchor = data["headers"][-1]
    return (anchor["wall_ns"] - anchor["mono_ns"]) - trace["start_wall_ns"]


def main_thread_spans(run: dict) -> list[dict]:
    """The main thread's spans with ``t0`` and ``t1`` in trace ns, open ones
    included (they end at their flush)."""
    shift = to_trace_ns(run)
    if shift is None:
        return []
    return [
        {**s, "t0": s["t0"] * 1e9 + shift, "t1": s["t1"] * 1e9 + shift}
        for s in of_run(run)["spans"] if s.get("thread") == MAIN_THREAD
    ]


def device_0(run: dict) -> xplane.DeviceTrace | None:
    """Device 0 of the run's reduced trace, if it ran an op (a CPU trace has
    no device plane)."""
    trace = run.get("trace")
    if not trace or not trace["devices"] or not trace.get("window_ns"):
        return None
    return trace["devices"][0] if trace["devices"][0].ops else None


def idle_gaps_of(run: dict) -> list[tuple[float, float]]:
    """Device 0's idle stretches inside the reduced trace window, as
    ``(start, end)`` in trace ns."""
    device = device_0(run)
    if device is None:
        return []
    lo, hi = run["trace"]["window_ns"]
    busy = xplane.merged(device.ops)
    edges = [lo, *[t for s, e in busy for t in (max(s, lo), min(e, hi))], hi]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def overlap(gaps: list[tuple[float, float]], span: dict) -> float:
    return sum(
        max(0.0, min(end, span["t1"]) - max(start, span["t0"]))
        for start, end in gaps
    )


def idle_by_span(run: dict) -> dict[str, float] | None:
    """Seconds of device 0's idle time in the trace window by the innermost
    main-thread span open meanwhile (``none`` where no span was): what the
    host was doing while the device had nothing. A gap that begins in one
    span and ends in another is split between them."""
    gaps, spans = idle_gaps_of(run), main_thread_spans(run)
    if not spans or device_0(run) is None:
        return None
    out = {"none": sum(end - start for start, end in gaps)}
    for span in spans:
        own = overlap(gaps, span)
        if own <= 0.0:
            continue
        out[span["name"]] = out.get(span["name"], 0.0) + own
        # what a span covers is no longer its parent's, nor nobody's
        parents = [p for p in spans if p["id"] == span.get("parent") and p["pid"] == span["pid"]]
        outer = parents[0]["name"] if parents else "none"
        out[outer] = out.get(outer, 0.0) - own
    return {
        name: ns / 1e9 for name, ns in sorted(out.items(), key=lambda kv: -kv[1])
        if ns > 0.5  # under a nanosecond: the float dust of the subtractions
    }


def idle_in_steps_pct(run: dict) -> float | None:
    """Share of the reduced trace window in which device 0 ran no op, less
    the idle stretches that are a boundary's, a checkpoint's or a
    preemption's doing: idle that is the step loop's own. A stretch is a
    boundary's if it touches the boundary's host work, from the end of
    ``boundary/loss_fetch`` (the device has run dry and the host knows it) to
    the end of ``boundary``; so is the whole stretch around it, the fetch's
    latency before and the first dispatch's after. While the host only waits
    in the fetch, with steps still queued, an idle device is the steps' own."""
    gaps, spans = idle_gaps_of(run), main_thread_spans(run)
    if not spans or device_0(run) is None:
        return None
    lo, hi = run["trace"]["window_ns"]
    fetch_end = {
        (s["pid"], s["parent"]): s["t1"] for s in spans
        if s["name"] == "boundary/loss_fetch"
    }
    excused = [
        (fetch_end.get((s["pid"], s["id"]), s["t0"]) if s["name"] == "boundary" else s["t0"], s["t1"])
        for s in spans if s["name"] in NOT_THE_STEP_LOOP
    ]
    own = sum(
        end - start for start, end in gaps
        if not any(start <= e and s <= end for s, e in excused)
    )
    return 100.0 * own / (hi - lo)


# -- a run directory, for a reader outside the benchmark -----------------------
def report(run_dir: str) -> dict:
    headers, spans = read_file(os.path.join(run_dir, SPANS_NAME))
    parts: dict[str, list[float]] = {}
    for boundary in named(spans, "boundary"):
        for child in children(spans, boundary):
            parts.setdefault(child["name"], []).append(self_time(spans, child))
        parts.setdefault("(self)", []).append(self_time(spans, boundary))
    # the median over the run's boundaries: the first one compiles
    medians = {name: statistics.median(values) for name, values in parts.items()}
    index = by_id(spans)
    return {
        "flushes": len(headers),
        "spans": len(spans),
        "boot_s": {
            s["name"]: round(s["t1"] - s["t0"], 6)
            for s in spans if s["name"].startswith("boot/")
        },
        "boundary_children_self_ms": {
            k: round(1e3 * v, 4) for k, v in sorted(medians.items(), key=lambda kv: -kv[1])
        },
        "preempt_s": {
            s["name"]: round(s["t1"] - s["t0"], 6)
            for s in spans
            if s["name"].startswith("preempt") or has_ancestor(index, s, "preempt")
        },
    }


if __name__ == "__main__":
    print(json.dumps(report(sys.argv[1]), indent=1))
