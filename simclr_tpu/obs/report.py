"""Post-mortem run reports: ``python -m simclr_tpu.obs.report <run_dir>``.

Merges everything a finished (or dead) run left behind — the
``events.jsonl`` timeline, the final ``heartbeat.json`` with its
telemetry snapshot, ``supervisor_summary.json`` when the run was
supervised — into one per-attempt post-mortem, and judges the run's
throughput against a named ``BENCH_*.json`` baseline:

    python -m simclr_tpu.obs.report results/run --baseline BENCH_TPU_CAPTURE.json

The last output line is always machine-greppable::

    run_report verdict: OK|REGRESSION|NO_BASELINE|NO_DATA (...)

``OK``/``REGRESSION`` mean a measured-vs-baseline imgs/sec/chip ratio
was actually computed (``REGRESSION`` when it falls below
``--threshold``); ``NO_BASELINE``/``NO_DATA`` mean the comparison could
not happen.  The CLI exits 0 whenever a report was produced — the
verdict line, not the exit code, carries the judgement.

Deliberately jax-free (stdlib + ``obs.events`` + ``supervisor.heartbeat``,
both stdlib-only) so it runs on any machine holding the run directory.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

from simclr_tpu.obs.events import events_path, read_events_counted
from simclr_tpu.supervisor.heartbeat import heartbeat_path, read_heartbeat

# a fleet whose slowest host runs >25% behind its fastest is flagged: SPMD
# collectives make every step as slow as the slowest participant, so this
# much skew is pure fleet-wide waste
SKEW_FLAG_RATIO = 1.25

VERDICT_OK = "OK"
VERDICT_REGRESSION = "REGRESSION"
VERDICT_NO_BASELINE = "NO_BASELINE"
VERDICT_NO_DATA = "NO_DATA"

DEFAULT_THRESHOLD = 0.8

SUMMARY_NAME = "supervisor_summary.json"

_COUNTED_EVENTS = {
    "epoch": "epochs",
    "checkpoint": "checkpoints",
    "slow_step": "slow_steps",
    "stall": "stalls",
    "auto_trace": "auto_traces",
    "nan_rollback": "nan_rollbacks",
    "preempt": "preempts",
    "compile": "compiles",
    "recompile_alarm": "recompile_alarms",
    "oom": "ooms",
    "host_lost": "hosts_lost",
    "remesh": "remeshes",
    "grow_back": "grow_backs",
    "swap": "swaps",
    "swap_rejected": "swap_rejections",
    "reallocate": "reallocations",
}

COSCHED_SUMMARY_NAME = "cosched_summary.json"


def _load_json(path: str) -> dict | None:
    try:
        with open(path, encoding="utf-8") as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return None
    return payload if isinstance(payload, dict) else None


def load_baseline(path: str) -> float | None:
    """imgs/sec/chip out of a ``BENCH_*.json`` artifact, or None.

    Handles both shapes the bench tooling writes: the committed capture
    (``{"payload": {"metric": "pretrain_imgs_per_sec_per_chip",
    "value": ...}}``) and a raw probe attempt (``{"parsed": {...}}`` —
    whose ``parsed`` is null when the probe died).
    """
    payload = _load_json(path)
    if payload is None:
        return None
    node = payload.get("payload") or payload.get("parsed") or payload
    if not isinstance(node, dict):
        return None
    if node.get("metric") == "pretrain_imgs_per_sec_per_chip":
        value = node.get("value")
    else:
        value = node.get("imgs_per_sec_per_chip")
    try:
        value = float(value)
    except (TypeError, ValueError):
        return None
    return value if value > 0 else None


def _fleet_hosts(run_dir: str) -> dict[str, dict]:
    """One row per per-host heartbeat file: the post-mortem's view of each
    host's last known step/epoch/step-time (``heartbeat.json`` is host 0,
    ``heartbeat.p<i>.json`` is host ``i``)."""
    hosts: dict[str, dict] = {}
    for path in sorted(glob.glob(os.path.join(run_dir, "heartbeat*.json"))):
        base = os.path.basename(path)
        if base == "heartbeat.json":
            index = 0
        else:
            match = re.fullmatch(r"heartbeat\.p(\d+)\.json", base)
            if not match:
                continue
            index = int(match.group(1))
        beat = read_heartbeat(path)
        if beat is None:
            continue
        telemetry = beat.get("telemetry")
        telemetry = telemetry if isinstance(telemetry, dict) else {}
        hosts[str(index)] = {
            "step": beat.get("step"),
            "epoch": beat.get("epoch"),
            "status": beat.get("status"),
            "beat_time": beat.get("time"),
            "step_time_s": telemetry.get("step_time_s"),
            "imgs_per_sec": telemetry.get("imgs_per_sec"),
        }
    return hosts


def build_report(
    run_dir: str,
    *,
    baseline_path: str | None = None,
    threshold: float = DEFAULT_THRESHOLD,
) -> dict:
    # torn lines (a crash mid-O_APPEND truncates at most the tail line) are
    # skipped but COUNTED: the report must say the timeline is incomplete
    # instead of silently under-reporting or tracebacking on json.loads
    events, torn_lines = read_events_counted(events_path(run_dir))
    attempts: dict[int, dict] = {}
    for event in events:
        try:
            attempt = int(event.get("attempt", 1))
        except (TypeError, ValueError):
            attempt = 1
        entry = attempts.setdefault(
            attempt,
            {
                **{field: 0 for field in _COUNTED_EVENTS.values()},
                "exit": None,
                "hung": False,
                "first_time": None,
                "last_time": None,
                "compile_seconds": 0.0,
                "hbm_peak_per_device": {},
                "host_transitions": [],
            },
        )
        kind = event.get("event")
        if kind in _COUNTED_EVENTS:
            entry[_COUNTED_EVENTS[kind]] += 1
        if kind == "remesh":
            # per-attempt host timeline, rendered as "hosts: 2→1→2"
            transitions = entry["host_transitions"]
            before, after = event.get("hosts_before"), event.get("hosts_after")
            if isinstance(before, int) and not transitions:
                transitions.append(before)
            if isinstance(after, int):
                transitions.append(after)
        elif kind == "child_exit":
            entry["exit"] = event.get("exit")
            entry["hung"] = bool(event.get("hung"))
        if kind == "compile":
            seconds = event.get("seconds")
            if isinstance(seconds, (int, float)):
                entry["compile_seconds"] = round(
                    entry["compile_seconds"] + seconds, 6
                )
        elif kind == "hbm":
            per_device = event.get("per_device")
            if isinstance(per_device, dict):
                peaks = entry["hbm_peak_per_device"]
                for device, peak in per_device.items():
                    if isinstance(peak, (int, float)):
                        peaks[str(device)] = max(
                            peaks.get(str(device), 0), int(peak)
                        )
        when = event.get("time")
        if isinstance(when, (int, float)):
            if entry["first_time"] is None:
                entry["first_time"] = when
            entry["last_time"] = when
    for entry in attempts.values():
        if entry["first_time"] is not None:
            entry["duration_s"] = round(entry["last_time"] - entry["first_time"], 3)
        else:
            entry["duration_s"] = None

    stalled = sorted(
        a for a, entry in attempts.items() if entry["stalls"] or entry["hung"]
    )

    # run-level host timeline ("hosts: 2→1→2") stitched from the remesh
    # events in order; empty for non-elastic runs
    hosts_timeline: list[int] = []
    for event in events:
        if event.get("event") != "remesh":
            continue
        before, after = event.get("hosts_before"), event.get("hosts_after")
        if isinstance(before, int) and not hosts_timeline:
            hosts_timeline.append(before)
        if isinstance(after, int):
            hosts_timeline.append(after)

    heartbeat = read_heartbeat(heartbeat_path(run_dir))
    telemetry = None
    if heartbeat is not None and isinstance(heartbeat.get("telemetry"), dict):
        telemetry = heartbeat["telemetry"]
    supervisor = _load_json(os.path.join(run_dir, SUMMARY_NAME))

    # co-scheduled serve plane: checkpoint hot-swaps, rejected swaps, and
    # train/serve device reallocations interleave with the training events
    # in the same run dir — the combined train+serve post-mortem
    cosched = _load_json(os.path.join(run_dir, COSCHED_SUMMARY_NAME))
    swap_events = [e for e in events if e.get("event") == "swap"]
    reject_events = [e for e in events if e.get("event") == "swap_rejected"]
    realloc_events = [e for e in events if e.get("event") == "reallocate"]
    serve = None
    if swap_events or reject_events or realloc_events or cosched:
        serve = {
            "swaps": len(swap_events),
            "swap_rejections": len(reject_events),
            "reallocations": sum(
                1 for e in realloc_events if e.get("direction") == "shrink"
            ),
            "releases": sum(
                1 for e in realloc_events if e.get("direction") == "release"
            ),
            "serving_generation": (
                swap_events[-1].get("generation")
                if swap_events
                else (cosched or {}).get("serving_generation", 0)
            ),
            "last_swap_epoch": (
                swap_events[-1].get("epoch") if swap_events else None
            ),
            "serve_replicas": (cosched or {}).get("serve_replicas"),
            "corpus_generation": (cosched or {}).get("corpus_generation"),
            "corpus_rows": (cosched or {}).get("corpus_rows"),
        }

    # fleet view: one row per heartbeat.p<i>.json (every host beats), the
    # skew/slowest verdict from the supervisor's embedded FleetCollector
    # snapshot when present, recomputed from the beats otherwise
    hosts = _fleet_hosts(run_dir)
    fleet = (
        supervisor.get("fleet")
        if supervisor and isinstance(supervisor.get("fleet"), dict)
        else None
    )
    skew, slowest = None, None
    if fleet is not None:
        skew = fleet.get("step_time_skew_ratio") or None
        slowest = fleet.get("slowest_host")
    if skew is None:
        step_times = {
            h: row["step_time_s"]
            for h, row in hosts.items()
            if isinstance(row.get("step_time_s"), (int, float))
            and row["step_time_s"] > 0
        }
        if step_times:
            slowest = max(step_times, key=step_times.get)
            skew = round(step_times[slowest] / min(step_times.values()), 4)

    measured = None
    if telemetry is not None:
        try:
            measured = float(telemetry.get("imgs_per_sec_per_chip"))
        except (TypeError, ValueError):
            measured = None
        if measured is not None and measured <= 0:
            measured = None

    baseline = load_baseline(baseline_path) if baseline_path else None

    ratio = None
    if not events and heartbeat is None:
        verdict = VERDICT_NO_DATA
    elif baseline is None:
        verdict = VERDICT_NO_BASELINE
    elif measured is None:
        verdict = VERDICT_NO_DATA
    else:
        ratio = measured / baseline
        verdict = VERDICT_OK if ratio >= threshold else VERDICT_REGRESSION

    return {
        "run_dir": os.path.abspath(run_dir),
        "attempts": {str(a): attempts[a] for a in sorted(attempts)},
        "stalled_attempts": stalled,
        "hosts_timeline": hosts_timeline,
        "torn_lines": torn_lines,
        "hosts": hosts,
        "fleet": fleet,
        "step_time_skew_ratio": skew,
        "slowest_host": slowest,
        "outcome": supervisor.get("outcome") if supervisor else None,
        "supervisor": supervisor,
        "serve": serve,
        "cosched": cosched,
        "heartbeat": heartbeat,
        "telemetry": telemetry,
        "measured_imgs_per_sec_per_chip": measured,
        "baseline_imgs_per_sec_per_chip": baseline,
        "threshold": threshold,
        "throughput_ratio": round(ratio, 4) if ratio is not None else None,
        "verdict": verdict,
    }


def render_report(report: dict) -> str:
    lines = [f"run report: {report['run_dir']}"]
    if report["outcome"] is not None:
        supervisor = report["supervisor"] or {}
        lines.append(
            f"outcome: {report['outcome']} "
            f"(exit {supervisor.get('exit')}, "
            f"resumed {supervisor.get('resumed', 0)}x)"
        )
    if report.get("hosts_timeline"):
        lines.append(
            "hosts: " + "→".join(str(n) for n in report["hosts_timeline"])
        )
    if report.get("torn_lines"):
        lines.append(
            f"WARNING: {report['torn_lines']} torn event line(s) skipped "
            "(events.jsonl truncated mid-write)"
        )
    for attempt, entry in report["attempts"].items():
        duration = (
            f"{entry['duration_s']:.1f}s"
            if entry["duration_s"] is not None
            else "?"
        )
        exit_part = "" if entry["exit"] is None else f" exit={entry['exit']}"
        hung_part = " HUNG" if entry["hung"] else ""
        lines.append(
            f"attempt {attempt}: {duration} epochs={entry['epochs']} "
            f"checkpoints={entry['checkpoints']} "
            f"slow_steps={entry['slow_steps']} stalls={entry['stalls']} "
            f"auto_traces={entry['auto_traces']} "
            f"nan_rollbacks={entry['nan_rollbacks']} "
            f"preempts={entry['preempts']}{exit_part}{hung_part}"
        )
        if entry["compiles"] or entry["recompile_alarms"] or entry["ooms"]:
            alarm_part = (
                f" RECOMPILE_ALARMS={entry['recompile_alarms']}"
                if entry["recompile_alarms"] else ""
            )
            oom_part = f" OOMS={entry['ooms']}" if entry["ooms"] else ""
            lines.append(
                f"  compiles: {entry['compiles']} "
                f"({entry['compile_seconds']:.2f}s total)"
                f"{alarm_part}{oom_part}"
            )
        if entry.get("hosts_lost") or entry.get("grow_backs"):
            transition = (
                " hosts: "
                + "→".join(str(n) for n in entry["host_transitions"])
                if entry.get("host_transitions") else ""
            )
            lines.append(
                f"  elastic: hosts_lost={entry['hosts_lost']} "
                f"remeshes={entry['remeshes']} "
                f"grow_backs={entry['grow_backs']}{transition}"
            )
        if entry["hbm_peak_per_device"]:
            peaks = " ".join(
                f"dev{device}={peak / 2 ** 30:.2f}GiB"
                for device, peak in sorted(entry["hbm_peak_per_device"].items())
            )
            lines.append(f"  hbm peak: {peaks}")
    if report["stalled_attempts"]:
        lines.append(
            "stalled attempts: "
            + ", ".join(str(a) for a in report["stalled_attempts"])
        )
    hosts = report.get("hosts") or {}
    if len(hosts) > 1 or report.get("fleet") is not None:
        skew = report.get("step_time_skew_ratio")
        if skew is not None:
            verdict = "STRAGGLER" if skew > SKEW_FLAG_RATIO else "even"
            skew_part = (
                f" skew={skew:.2f}x ({verdict},"
                f" slowest=host {report.get('slowest_host')})"
            )
        else:
            skew_part = ""
        fleet = report.get("fleet") or {}
        up_part = (
            f" up={fleet['hosts_up']}/{fleet['hosts_expected']}"
            if "hosts_up" in fleet else ""
        )
        lines.append(f"fleet: hosts={len(hosts)}{up_part}{skew_part}")
        for host, row in sorted(hosts.items(), key=lambda kv: int(kv[0])):
            step_time = (
                f"{row['step_time_s']:.4f}s"
                if isinstance(row.get("step_time_s"), (int, float))
                else "?"
            )
            rate = (
                f"{row['imgs_per_sec']:.1f}"
                if isinstance(row.get("imgs_per_sec"), (int, float))
                else "?"
            )
            lines.append(
                f"  host {host}: step={row.get('step')} "
                f"epoch={row.get('epoch')} step_time={step_time} "
                f"imgs/s={rate}"
            )
        trace = os.path.join(report["run_dir"], "timeline_trace.json")
        if os.path.exists(trace):
            lines.append(f"timeline: {trace}")
        else:
            lines.append(
                "timeline: python -m simclr_tpu.obs.timeline "
                f"{report['run_dir']}"
            )
    serve = report.get("serve")
    if serve:
        reject_part = (
            f" REJECTED={serve['swap_rejections']}"
            if serve.get("swap_rejections") else ""
        )
        replica_part = (
            f" replicas={serve['serve_replicas']}"
            if serve.get("serve_replicas") is not None else ""
        )
        corpus_part = (
            f" corpus=gen{serve['corpus_generation']}/"
            f"{serve.get('corpus_rows')}rows"
            if serve.get("corpus_generation") is not None else ""
        )
        lines.append(
            f"serve: swaps={serve['swaps']}{reject_part} "
            f"generation={serve.get('serving_generation')} "
            f"reallocations={serve['reallocations']} "
            f"(released {serve['releases']}){replica_part}{corpus_part}"
        )
        if serve.get("last_swap_epoch") is not None:
            lines.append(f"  last swap: epoch {serve['last_swap_epoch']}")
    detail = (
        f"imgs/s/chip measured={report['measured_imgs_per_sec_per_chip']} "
        f"baseline={report['baseline_imgs_per_sec_per_chip']} "
        f"ratio={report['throughput_ratio']} "
        f"threshold={report['threshold']}"
    )
    # keep this the LAST line and the format stable: tooling greps
    # '^run_report verdict: '
    lines.append(f"run_report verdict: {report['verdict']} ({detail})")
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m simclr_tpu.obs.report",
        description="Per-attempt post-mortem of a run directory with a "
        "throughput-regression verdict.",
    )
    parser.add_argument("run_dir", help="run save_dir holding events.jsonl etc.")
    parser.add_argument(
        "--baseline",
        default=None,
        help="BENCH_*.json artifact holding the imgs/sec/chip baseline",
    )
    parser.add_argument(
        "--threshold",
        type=float,
        default=DEFAULT_THRESHOLD,
        help="minimum measured/baseline ratio judged OK (default %(default)s)",
    )
    parser.add_argument(
        "--json", default=None, help="also write the full report to this path"
    )
    args = parser.parse_args(argv)

    report = build_report(
        args.run_dir, baseline_path=args.baseline, threshold=args.threshold
    )
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")
    print(render_report(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
