"""Compile-side observability: cost extraction, compile records, recompile
sentry.

The host-side telemetry (PR 6/7) sees the run through step wall times; this
module watches the *compiler* boundary — the other place a TPU run silently
loses its performance:

* **Cost attribution** — :func:`executable_cost` extracts XLA's analytic
  flops / bytes-accessed from an AOT-compiled executable (promoted from
  ``scripts/perf_attrib.py`` so the one-off attribution script and the live
  telemetry share one extraction), and the per-executable numbers are
  exported as labeled gauges plus a roofline-vs-XLA MFU drift signal.
* **Compile records** — every lower+compile is timed, fingerprinted
  (sha256 of the lowered StableHLO/jaxpr text), and emitted as a
  ``compile`` event into ``events.jsonl`` — wall time, fingerprint,
  analytic cost in one line.
* **Recompile sentry** — a step function that recompiles after warmup is
  the classic silent TPU perf killer (a shape or dtype drifted and every
  step now pays a multi-second compile). :meth:`CompileSentry.watch` wraps
  a jitted step in an explicit AOT lower/compile cache keyed by the
  abstract argument signature, so any post-warmup compilation is observed
  the moment it happens: alarm counter + ``recompile_alarm`` event +
  the PR 7 rate-limited auto-trace hook.
* **Scope map** — a device trace names each executed op by its
  instruction (``%fusion.33 = ...``) and, read through
  ``jax.profiler.ProfileData``, carries no ``op_name``; the compiled
  program's text does (``metadata={op_name="jit(step)/encoder/..."}``, the
  ``jax.named_scope`` path). :func:`instruction_scopes` reads it once per
  compile into ``<save_dir>/step_scopes.json``, which lets a reader
  (``benchmarks/scopes.py``) set each op of a trace to augment / encoder /
  head / ntxent / grad_reduce / lars.

``watch`` takes the AOT path (``fn.lower(*args).compile()`` — the only
way to both time a compile precisely and keep the executable for
fingerprint/cost analysis); a lowering or compile error propagates. Only a
wrapped callable that has no ``lower`` at all is dispatched plainly with
call-duration timing. Stdlib-only at import time; jax is imported lazily
inside the signature helper.
"""

from __future__ import annotations

import collections
import hashlib
import json
import logging
import os
import re
import threading
import time

from simclr_tpu.obs.trace import recorder
from simclr_tpu.utils.ioutil import atomic_write

logger = logging.getLogger("simclr_tpu")

COMPILE_EVENT = "compile"
RECOMPILE_ALARM_EVENT = "recompile_alarm"


# {instruction name: op_name} of the newest watched program, beside events.jsonl
STEP_SCOPES_NAME = "step_scopes.json"

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([^\s(]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FUSION_CALLS = re.compile(r" fusion\(.*?calls=%?([^\s,}]+)")
# an instruction that is its called computations' instructions one after
# another: a loop, a branch, a call
_RUNS_OTHERS = re.compile(r" (?:while|conditional|call)\(")
_OPERAND = re.compile(r"%([^\s,()]+)")


def instruction_scopes(hlo_text: str) -> dict[str, str]:
    """``{instruction name: op_name}`` of a compiled program's HLO text, for
    every instruction a device trace can show: those of the entry computation
    and of the computations it calls or loops over, not the insides of
    fusions. A fusion that carries no ``op_name`` itself takes its root
    instruction's (xprof's rule), or where the root has none, the one most of
    its instructions carry. An instruction the compiler added without
    metadata (a prefetch copy and its ``-start``/``-done``, a slice, a
    reshape) moves what its first operand made, and takes that one's
    ``op_name``. An instruction printed over several lines (a Pallas custom
    call) is read to its last line. A loop, a branch or a call is left out:
    a device trace shows it as one event over all the events of what it
    runs, and those are in the map one by one, so a reader that sums time by
    scope would count the loop's inside twice (the chunk loops of
    ``models/experts.py``: 236 ms of a 910 ms step; chip run, PR 35). What
    still has none (constants, parameters' copies) is left out too: a reader
    counts it as unscoped."""
    computations: dict[str, list[tuple]] = {}
    runs_others = set()
    current = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            if m:
                current = computations.setdefault(m.group(1), [])
            elif line.strip() == "}" or current is None:
                current = None
            elif current:
                # the text of one instruction going on: a Pallas kernel's
                # custom call prints its kernel metadata over several lines
                # that begin in the first column, and its ``metadata={op_name``
                # on the last of them. It neither ends the computation nor
                # loses its name
                op_name = _OP_NAME.search(line)
                if op_name and current[-1][1] is None:
                    current[-1] = (current[-1][0], op_name.group(1), *current[-1][2:4], None)
            continue
        m = _INSTRUCTION.match(line) if current is not None else None
        if m is None:
            continue
        op_name = _OP_NAME.search(line)
        calls = _FUSION_CALLS.search(line)
        operand = None if op_name else _OPERAND.search(line, m.end())
        if _RUNS_OTHERS.search(line, m.end()):
            runs_others.add(m.group(2))
        current.append((
            m.group(2), op_name.group(1) if op_name else None,
            bool(m.group(1)), calls.group(1) if calls else None,
            operand.group(1) if operand else None,
        ))
    fused = {
        calls for rows in computations.values() for *_, calls, _ in rows if calls
    }

    def fusion_op_name(computation: str, depth: int = 0) -> str | None:
        rows = computations.get(computation, ())
        for _, op_name, is_root, calls, _ in rows:
            if is_root and not op_name and calls and depth < 4:
                op_name = fusion_op_name(calls, depth + 1)  # a fusion of fusions
            if is_root and op_name:
                return op_name
        # a root the compiler made (the tuple of a multi-output fusion, a
        # copy): the op_name most of the fused instructions carry
        names = collections.Counter(op for _, op, *_ in rows if op)
        return names.most_common(1)[0][0] if names else None  # ties: the first

    scopes, moved_from = {}, {}
    for computation, rows in computations.items():
        if computation in fused:
            continue
        for name, op_name, _, calls, operand in rows:
            if op_name is None and calls:
                op_name = fusion_op_name(calls)
            if op_name:
                scopes[name] = op_name
            elif operand:
                moved_from[name] = operand
    for name in moved_from:
        source = name
        for _ in range(8):  # copy-done <- copy-start <- the producer
            source = moved_from.get(source)
            if source is None or source in scopes:
                break
        if source in scopes:
            scopes[name] = scopes[source]
    # only now: what moves a loop's result still takes the loop's name
    return {name: op for name, op in scopes.items() if name not in runs_others}


def executable_cost(compiled) -> tuple[float, float]:
    """(flops, bytes_accessed) from an AOT executable's XLA cost analysis.

    The extraction ``scripts/perf_attrib.py`` used privately, promoted so
    live telemetry and the attribution script agree by construction. A
    backend whose analysis omits a key reports 0.0 for it.
    """
    ca = compiled.cost_analysis() or {}
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


def lowered_fingerprint(lowered) -> str:
    """Stable hex fingerprint of a lowered program's StableHLO/jaxpr text.

    Two lowerings of the same function at the same abstract signature hash
    identically, so a changed fingerprint in a ``compile`` event names a
    genuinely different program, not a re-run.
    """
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


def args_signature(args: tuple) -> tuple:
    """Hashable abstract signature of a call's arguments.

    Array leaves contribute ``(shape, dtype)``; non-array leaves (python
    scalars — jit's weak types) contribute their type only, so a step
    counter changing value does not look like a new program. This is the
    compile-cache key the sentry's AOT cache shares with jit's own
    dispatch logic for our purposes: same signature, same executable.
    """
    import jax

    def leaf_sig(x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is None or dtype is None:
            return (type(x).__name__,)
        return (tuple(shape), str(dtype))

    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (tuple(leaf_sig(leaf) for leaf in leaves), str(treedef))


class CompileSentry:
    """Registry of observed XLA compilations for one run.

    ``record_compile`` is the single funnel: it counts, emits the
    ``compile`` event, pushes cost numbers into telemetry, and — when the
    compile happened after warmup (``warm=True``) — raises the recompile
    alarm (counter + ``recompile_alarm`` event + auto-trace hook).
    ``auto_trace`` is the detector's ``_maybe_auto_trace(reason, seconds)``
    bound method (PR 7), so alarms share its cooldown and budget.
    """

    def __init__(
        self,
        *,
        telemetry=None,
        events=None,
        auto_trace=None,
        clock=time.perf_counter,
        scopes_dir: str | None = None,
    ):
        self.telemetry = telemetry
        self.events = events
        # where step_scopes.json goes (the run directory); None writes none
        self.scopes_dir = scopes_dir
        self._auto_trace = auto_trace
        self._clock = clock
        self._lock = threading.Lock()
        self.records: list[dict] = []
        self.compiles = 0
        self.recompile_alarms = 0

    def record_compile(
        self,
        name: str,
        *,
        seconds: float,
        fingerprint: str = "",
        flops: float = 0.0,
        bytes_accessed: float = 0.0,
        warm: bool = False,
        cache_hit: bool | None = None,
    ) -> dict:
        """Book one observed compilation of executable ``name``.

        Every watched executable runs one step per call, so its cost is the
        per-step cost the MFU gauge divides. ``warm=True`` marks a
        post-warmup compilation and fires the alarm path. ``cache_hit``
        says whether JAX's persistent compilation cache supplied the
        executable (``seconds`` is then lowering plus a load, not a
        compile); ``None`` when the caller cannot tell.
        """
        record = {
            "name": str(name),
            "seconds": float(seconds),
            "fingerprint": fingerprint,
            "flops": float(flops),
            "bytes_accessed": float(bytes_accessed),
            "recompile": bool(warm),
        }
        known = {} if cache_hit is None else {"cache_hit": bool(cache_hit)}
        record.update(known)
        with self._lock:
            self.records.append(record)
            self.compiles += 1
            if warm:
                self.recompile_alarms += 1
        if self.telemetry is not None:
            self.telemetry.record_compile(seconds)
            self.telemetry.observe_xla_cost(
                name,
                flops_per_step=record["flops"],
                bytes_per_step=record["bytes_accessed"],
            )
        if self.events is not None:
            self.events.emit(
                COMPILE_EVENT,
                name=record["name"],
                seconds=round(record["seconds"], 6),
                fingerprint=fingerprint,
                flops=record["flops"],
                bytes_accessed=record["bytes_accessed"],
                recompile=bool(warm),
                **known,
            )
        if warm:
            if self.telemetry is not None:
                self.telemetry.record_recompile_alarm()
            if self.events is not None:
                self.events.emit(
                    RECOMPILE_ALARM_EVENT,
                    name=record["name"],
                    seconds=round(record["seconds"], 6),
                    fingerprint=fingerprint,
                )
            if self._auto_trace is not None:
                try:
                    self._auto_trace(RECOMPILE_ALARM_EVENT, record["seconds"])
                except Exception:
                    pass
        return record

    def write_scopes(self, compiled) -> None:
        """``step_scopes.json`` for this executable, inside the compile's
        span and seconds (0.28 s to print the ResNet-18 step's 5 MB of HLO
        on the v5e, 0.08 s to read it; chip run, PR 25). A failure costs the
        trace reader its scopes and the run nothing."""
        if self.scopes_dir is None:
            return
        try:
            t0 = time.perf_counter()
            text = compiled.as_text()
            t1 = time.perf_counter()
            scopes = instruction_scopes(text)
            t2 = time.perf_counter()
            os.makedirs(self.scopes_dir, exist_ok=True)
            atomic_write(
                os.path.join(self.scopes_dir, STEP_SCOPES_NAME),
                lambda f: json.dump(scopes, f),
            )
            logger.info(
                "%s: %d instructions (text %.2fs, read %.2fs, written %.2fs)",
                STEP_SCOPES_NAME, len(scopes), t1 - t0, t2 - t1,
                time.perf_counter() - t2,
            )
        except Exception as e:  # a backend without as_text, a full disk
            logger.warning("%s not written: %r", STEP_SCOPES_NAME, e)

    def watch(self, fn, name: str):
        """Wrap a jitted callable so its every compilation is observed."""
        return WatchedFunction(fn, name, self)


class WatchedFunction:
    """AOT lower/compile wrapper around one jitted step function.

    Keeps its own signature-keyed executable cache — each new abstract
    signature triggers an explicit ``lower`` + timed ``compile`` whose
    executable is fingerprinted and cost-analyzed, then cached; repeat
    signatures dispatch straight to the cached executable. Donation and
    sharding are captured at lowering, so the compiled program behaves
    exactly like the jit dispatch it replaces. A signature seen after the
    first completed call means the step function recompiled after warmup —
    the sentry's alarm condition.

    Called from the single training-loop thread (matching how the step
    functions it wraps are used); the sentry's own bookkeeping is locked.
    """

    def __init__(self, fn, name: str, sentry: CompileSentry):
        self._fn = fn
        self.name = str(name)
        self._sentry = sentry
        self._cache: dict = {}
        self._calls = 0

    def __call__(self, *args):
        entry = self._cache.get(args_signature(args))
        if entry is None:
            if not hasattr(self._fn, "lower"):
                return self._first_plain_call(args)
            entry = self.compile(*args)
        self._calls += 1
        return entry(*args)

    def _first_plain_call(self, args):
        """Not a jitted function: dispatch plainly — the first call at a new
        signature still IS the compiling call, so its duration is the
        (upper-bound) compile time."""
        clock = self._sentry._clock
        t0 = clock()
        out = self._fn(*args)
        self._sentry.record_compile(
            self.name,
            seconds=clock() - t0,
            warm=self._calls > 0,
        )
        self._cache[args_signature(args)] = self._fn
        self._calls += 1
        return out

    def compile(self, *args):
        """The executable for these arguments' signature, lowered, compiled
        and recorded on a miss. The host loop calls this before its first
        dispatch, so that the compilation is a span of its own
        (``boot/compile``) and the first ``dispatch`` span is a dispatch."""
        sig = args_signature(args)
        entry = self._cache.get(sig)
        if entry is not None or not hasattr(self._fn, "lower"):
            return entry
        from simclr_tpu.utils.platform import compilation_cache_stats

        clock = self._sentry._clock
        with recorder().span("boot/compile"):
            t0 = clock()
            cache_hits = compilation_cache_stats()["hits"]
            lowered = self._fn.lower(*args)
            compiled = lowered.compile()
            cache_hit = compilation_cache_stats()["hits"] > cache_hits
            flops, bytes_accessed = executable_cost(compiled)
            self._sentry.write_scopes(compiled)
            self._sentry.record_compile(
                self.name,
                seconds=clock() - t0,
                fingerprint=lowered_fingerprint(lowered),
                flops=flops,
                bytes_accessed=bytes_accessed,
                warm=self._calls > 0,
                cache_hit=cache_hit,
            )
        self._cache[sig] = compiled
        return compiled


def maybe_sentry(cfg, *, telemetry=None, events=None, detector=None):
    """Config-gated constructor used by the trainers (process 0 only).

    Reuses the anomaly detector's rate-limited auto-trace (cooldown +
    per-attempt budget) as the alarm's capture hook when one is running.
    """
    if not bool(cfg.select("telemetry.compile_sentry", True)):
        return None
    auto_trace = detector._maybe_auto_trace if detector is not None else None
    scopes_dir = None
    if events is not None and events.enabled:
        scopes_dir = os.path.dirname(events.path)
    return CompileSentry(
        telemetry=telemetry, events=events, auto_trace=auto_trace,
        scopes_dir=scopes_dir,
    )
