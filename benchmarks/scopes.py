"""Device time of the step program by what it does, from a trace and the
program's own map of its instructions.

The step program wraps its parts in ``jax.named_scope``: ``augment``,
``encoder`` (the projection ``head`` inside it), ``ntxent``, ``grad_reduce``,
``lars``. A scope is HLO metadata: every instruction of the compiled program
carries the path of the JAX primitive it came from
(``op_name="jit(step)/transpose(jvp(encoder))/..."``; backward ops carry the
forward's scope inside ``transpose(``). The trace's ``XLA Ops`` events, read
through ``jax.profiler.ProfileData``, carry the instruction's text and no
``op_name`` (jax 0.9.0, libtpu 0.0.34: the event's stats are its offset and
duration; chip run, PR 25). So the trainer writes the map itself, once per
compile: ``<run_dir>/step_scopes.json``, ``{instruction name: op_name}``. A
fusion has the ``op_name`` of its root instruction (xprof's rule), and an
instruction the compiler added without metadata (a prefetch copy, a slice of
its own) that of the instruction whose result it moves. What the map does not
hold, or holds under no scope's name, is counted as ``unscoped``.

Counted on device 0, inside the reduced window, over the whole executions of
the step program (``xplane.step_module``): the ops inside each, over their
number. Ops on the ``XLA Ops`` line do not overlap, so the parts add up to the
device's busy time inside a step.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import statistics

from benchmarks import xplane

SCOPES_NAME = "step_scopes.json"
# the benchmark's own copy of the program's scope names: what a part is
# called is part of the yardstick
SCOPES = ("augment", "encoder", "head", "ntxent", "grad_reduce", "lars")
UNSCOPED = "unscoped"
# the parts reported: the encoder and its head split by direction
PARTS = {
    "augment": "augment", "ntxent": "loss", "grad_reduce": "grad_reduce",
    "lars": "optimizer",
}
_SPLIT = re.compile(r"[/()]")


def part_of(op_name: str | None) -> str:
    """``augment``, ``fwd``, ``bwd``, ``loss``, ``grad_reduce``,
    ``optimizer`` or ``unscoped`` for an instruction's ``op_name``: its
    innermost scope, the encoder's and the head's split by ``transpose(``."""
    if not op_name:
        return UNSCOPED
    scopes = [t for t in _SPLIT.split(op_name) if t in SCOPES]
    if not scopes:
        return UNSCOPED
    if scopes[-1] in ("encoder", "head"):
        return "bwd" if "transpose(" in op_name else "fwd"
    return PARTS[scopes[-1]]


def of_run(run: dict) -> dict | None:
    """The run's ``{instruction name: op_name}``, read once per run record;
    ``None`` where the trainer wrote none."""
    if "step_scopes" not in run:
        try:
            with open(os.path.join(run.get("run_dir") or "", SCOPES_NAME)) as f:
                scopes = json.load(f)
        except (OSError, ValueError):
            scopes = None
        run["step_scopes"] = scopes if isinstance(scopes, dict) and scopes else None
    return run["step_scopes"]


def whole_steps(device: xplane.DeviceTrace) -> list[tuple[float, float]]:
    """``(start, end)`` of the step program's whole executions. A capture
    ends where it ends: its last execution is usually cut short (51.6 ms of a
    126 ms step in the first trace read; chip run, PR 25) and would count as
    a step with a third of its ops, so an execution much shorter than the
    median one is left out."""
    module = xplane.step_module(device)
    if module is None:
        return []
    runs = [(s, s + d) for s, d, n in device.modules if n == module]
    typical = statistics.median(e - s for s, e in runs)
    return sorted((s, e) for s, e in runs if e - s >= 0.9 * typical)


def ms_per_step(device: xplane.DeviceTrace, scopes: dict) -> dict[str, float] | None:
    """Milliseconds of device time per step by part, ``unscoped`` among
    them: the ops inside the step program's whole executions, over their
    number. ``None`` without a step."""
    steps = whole_steps(device)
    if not steps:
        return None
    starts = [s for s, _ in steps]
    totals: dict[str, float] = {}
    for start, dur, name in device.ops:
        k = bisect.bisect_right(starts, start) - 1
        if k < 0 or start + dur > steps[k][1]:
            continue  # between two steps, or another program's
        part = part_of(scopes.get(xplane.parse_hlo(name)[0]))
        totals[part] = totals.get(part, 0.0) + dur
    return {part: ns / 1e6 / len(steps) for part, ns in totals.items()}


def read(run: dict, part: str) -> float | None:
    """One part's milliseconds per step on device 0 of the run's trace;
    ``None`` without a trace, without the map, or where the part never ran
    (the gradient reduce on one chip)."""
    trace, scopes = run.get("trace"), of_run(run)
    if not trace or not trace["devices"] or scopes is None:
        return None
    if "scope_ms" not in run:
        run["scope_ms"] = ms_per_step(trace["devices"][0], scopes)
    return (run["scope_ms"] or {}).get(part)
