"""Cross-host run timeline as Chrome/Perfetto trace-event JSON.

    python -m simclr_tpu.obs.timeline <run_dir> [-o trace.json]

Merges everything a run directory records about time — the
``events.jsonl`` stream (training epochs/checkpoints, supervisor
lifecycle, elastic ``host_lost``/``remesh``/``grow_back``), the per-host
``heartbeat.p<i>.json`` files, and ``supervisor_summary.json`` — into one
trace-event file that ``chrome://tracing`` or https://ui.perfetto.dev
renders as tracks:

  * one track (``pid``) per host slot, with epoch spans (``ph="X"``,
    duration from the event's ``seconds`` field) and instant markers for
    checkpoints, stalls, auto-traces, compiles and the host's last
    heartbeat. Trainer-emitted events come from the generation's logging
    host and are attributed to slot 0 (the lowest slot survives every
    fixture remesh and re-elects as rank 0);
  * a supervisor track carrying ``run_start``/``child_exit``/``restart``/
    ``remesh 2→1``/``grow_back``/``outcome`` lifecycle markers;
  * a serve track for ``serve_*`` events (e.g. a ``serve_swap`` span when
    the serving tier swaps weights mid-run);
  * under each host, one row per thread of the trainer's host spans
    (``spans.jsonl`` / ``spans.p<i>.jsonl``, ``obs/trace.py``): boot,
    dispatch, input wait, boundary, checkpoint and preempt spans with their
    ``step``, ``epoch`` and ``n``, set on the wall clock by the file's
    clock anchor.

Within one track the ``tid`` is the attempt (supervisor restart ordinal or
elastic generation), so attempts stack as separate rows under each host.
Timestamps are wall-clock microseconds rebased to the run's first event,
emitted sorted so every track is monotonic.

Stdlib-only by contract (plus ``obs.events`` + ``supervisor.heartbeat``,
both stdlib): the timeline renders anywhere the run directory is mounted.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

from simclr_tpu.obs.events import events_path, read_events_counted
from simclr_tpu.obs.trace import read_spans, spans_path
from simclr_tpu.supervisor.heartbeat import HEARTBEAT_NAME, read_heartbeat

TRACE_NAME = "timeline_trace.json"

# pid blocks: trace viewers group rows by pid, so each logical track gets
# a disjoint small integer
PID_SUPERVISOR = 1
PID_SERVE = 2
PID_HOST_BASE = 10  # host slot i renders as pid 10 + i
# attempts are tids 1, 2, ...; a host's span threads follow from here
TID_SPAN_BASE = 1000

# supervisor/lifecycle event kinds (everything the trainers do NOT emit)
_LIFECYCLE = {
    "run_start", "run_end", "outcome", "child_exit", "restart", "hang",
    "remesh", "grow_back", "topology_change", "reallocate",
}

# co-scheduler serve-plane kinds that don't carry the "serve" prefix
_SERVE_EVENTS = {"swap", "swap_rejected"}


def _num(value, default=None):
    return value if isinstance(value, (int, float)) else default


def _attempt(event: dict) -> int:
    try:
        return int(event.get("attempt", 1))
    except (TypeError, ValueError):
        return 1


def _event_name(event: dict) -> str:
    kind = event.get("event", "?")
    if kind == "epoch":
        return f"epoch {event.get('epoch', '?')}"
    if kind == "checkpoint":
        return f"checkpoint e{event.get('epoch', '?')}"
    if kind == "remesh":
        return (
            f"remesh {event.get('hosts_before', '?')}"
            f"→{event.get('hosts_after', '?')}"
        )
    if kind == "host_lost":
        return f"host_lost ({event.get('reason', '?')})"
    if kind == "grow_back":
        hosts = event.get("hosts")
        return f"grow_back {hosts}" if hosts else "grow_back"
    if kind == "outcome":
        return f"outcome: {event.get('outcome', '?')}"
    if kind == "swap":
        return f"swap e{event.get('epoch', '?')} → gen {event.get('generation', '?')}"
    if kind == "swap_rejected":
        return f"swap_rejected e{event.get('epoch', '?')}"
    if kind == "reallocate":
        return f"reallocate ({event.get('direction', '?')})"
    return str(kind)


def _track_for(event: dict) -> int:
    """Which pid an event renders under (see module doc)."""
    kind = str(event.get("event", ""))
    if kind == "host_lost" and _num(event.get("host")) is not None:
        return PID_HOST_BASE + int(event["host"])
    if kind.startswith("serve") or kind in _SERVE_EVENTS:
        return PID_SERVE
    if kind in _LIFECYCLE:
        return PID_SUPERVISOR
    # trainer-emitted: the generation's logging host, attributed to slot 0
    return PID_HOST_BASE + 0


def _host_slots(events: list[dict], run_dir: str) -> list[int]:
    """Every host slot the run ever touched: remesh host counts, explicit
    per-event host fields, grow_back lists, and heartbeat.p<i>.json files."""
    slots = {0}
    for event in events:
        for key in ("hosts_before", "hosts_after"):
            count = _num(event.get(key))
            if count is not None:
                slots.update(range(int(count)))
        host = _num(event.get("host"))
        if host is not None:
            slots.add(int(host))
        hosts = event.get("hosts")
        if isinstance(hosts, list):
            slots.update(int(h) for h in hosts if isinstance(h, int))
    for path in glob.glob(os.path.join(run_dir, "heartbeat*.json")):
        match = re.search(r"heartbeat\.p(\d+)\.json$", path)
        if match:
            slots.add(int(match.group(1)))
        elif os.path.basename(path) == HEARTBEAT_NAME:
            slots.add(0)
    return sorted(slots)


def build_timeline(run_dir: str) -> dict:
    """The trace-event document for one run directory.

    Always returns a valid (possibly near-empty) document; ``torn_lines``
    in ``otherData`` counts unparseable event lines that were skipped.
    """
    events, torn = read_events_counted(events_path(run_dir))
    timed = [e for e in events if _num(e.get("time")) is not None]
    slots = _host_slots(events, run_dir)

    heartbeats: dict[int, dict] = {}
    for slot in slots:
        name = HEARTBEAT_NAME if slot == 0 else f"heartbeat.p{slot}.json"
        beat = read_heartbeat(os.path.join(run_dir, name))
        if beat is not None and _num(beat.get("time")) is not None:
            heartbeats[slot] = beat

    host_spans = {
        slot: spans for slot in slots
        if (spans := read_spans(spans_path(run_dir, slot)))
    }

    base_candidates = [e["time"] for e in timed]
    base_candidates += [b["time"] for b in heartbeats.values()]
    base_candidates += [
        (s["t0"] + s["wall_offset_ns"]) / 1e9
        for spans in host_spans.values() for s in spans[:1]
    ]
    base = min(base_candidates) if base_candidates else 0.0

    def us(when: float) -> int:
        return max(0, int(round((when - base) * 1e6)))

    trace: list[dict] = []
    # process_name metadata rows label the tracks in the viewer
    names = {PID_SUPERVISOR: "supervisor", PID_SERVE: "serve"}
    names.update({PID_HOST_BASE + s: f"host {s}" for s in slots})
    for pid, label in sorted(names.items()):
        trace.append({
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": label},
        })

    body: list[dict] = []
    for event in timed:
        pid = _track_for(event)
        tid = _attempt(event)
        seconds = _num(event.get("seconds"))
        args = {
            k: v
            for k, v in event.items()
            if k not in ("event", "time", "monotonic") and v is not None
        }
        if seconds is not None and seconds > 0:
            # a span whose duration the event recorded (epoch, compile):
            # the event is stamped at the END of the interval
            body.append({
                "ph": "X", "name": _event_name(event), "pid": pid,
                "tid": tid, "ts": us(event["time"] - seconds),
                "dur": int(round(seconds * 1e6)), "args": args,
            })
        else:
            body.append({
                "ph": "i", "s": "t", "name": _event_name(event), "pid": pid,
                "tid": tid, "ts": us(event["time"]), "args": args,
            })
    for slot, beat in heartbeats.items():
        body.append({
            "ph": "i", "s": "t", "name": "last_heartbeat",
            "pid": PID_HOST_BASE + slot, "tid": _attempt(beat),
            "ts": us(beat["time"]),
            "args": {
                k: beat.get(k)
                for k in ("step", "epoch", "status")
                if beat.get(k) is not None
            },
        })
    for slot, spans in host_spans.items():
        pid = PID_HOST_BASE + slot
        threads = sorted({str(s.get("thread", "?")) for s in spans})
        for i, thread in enumerate(threads):
            trace.append({
                "ph": "M", "name": "thread_name", "pid": pid,
                "tid": TID_SPAN_BASE + i, "args": {"name": f"spans: {thread}"},
            })
        for span in spans:
            body.append({
                "ph": "X", "name": span["name"], "pid": pid,
                "tid": TID_SPAN_BASE + threads.index(str(span.get("thread", "?"))),
                "ts": us((span["t0"] + span["wall_offset_ns"]) / 1e9),
                "dur": max(0, int(round((span["t1"] - span["t0"]) / 1e3))),
                "args": {
                    k: span[k] for k in ("step", "epoch", "n", "open")
                    if span.get(k) is not None
                },
            })
    body.sort(key=lambda e: (e["ts"], e["pid"], e["tid"]))
    trace.extend(body)

    summary = None
    try:
        with open(os.path.join(run_dir, "supervisor_summary.json")) as f:
            payload = json.load(f)
        summary = payload if isinstance(payload, dict) else None
    except (OSError, ValueError):
        pass

    other = {"run_dir": os.path.abspath(run_dir), "torn_lines": torn}
    if summary is not None:
        for key in ("outcome", "remesh_count", "grow_back_count",
                    "hosts_timeline"):
            if key in summary:
                other[key] = summary[key]
    return {
        "traceEvents": trace,
        "displayTimeUnit": "ms",
        "otherData": other,
    }


def trace_path(run_dir: str) -> str:
    return os.path.join(run_dir, TRACE_NAME)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m simclr_tpu.obs.timeline",
        description="Merge a run directory's events/heartbeats into "
        "Chrome/Perfetto trace-event JSON (load at ui.perfetto.dev).",
    )
    parser.add_argument("run_dir", help="run save_dir holding events.jsonl")
    parser.add_argument(
        "-o", "--out", default=None,
        help=f"output path (default <run_dir>/{TRACE_NAME})",
    )
    args = parser.parse_args(argv)

    document = build_timeline(args.run_dir)
    out = args.out or trace_path(args.run_dir)
    with open(out, "w", encoding="utf-8") as f:
        json.dump(document, f)
        f.write("\n")
    spans = sum(1 for e in document["traceEvents"] if e["ph"] != "M")
    torn = document["otherData"]["torn_lines"]
    torn_part = f" ({torn} torn line(s) skipped)" if torn else ""
    print(f"timeline: {spans} events -> {out}{torn_part}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
