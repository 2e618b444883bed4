"""Reduce a JAX profiler trace (``.xplane.pb``) to what the benchmark reports.

One accelerator device is one plane (``/device:TPU:<n>``). On it, the line
``XLA Ops`` holds one event per executed HLO op (the event's name is the op's
HLO text, ``%fusion.33 = (...) fusion(...)``), ``XLA Modules`` one event per
executed program, and ``Async XLA Ops`` one event per asynchronous op from
its ``-start`` to its ``-done``. Times are nanoseconds since the capture
began. This layout was read off a capture on a v5e (PR 24, jax 0.9.0,
libtpu 0.0.34); a trace without such planes reduces to nothing, and the
callers then report nothing.

Reading the file needs only JAX (``jax.profiler.ProfileData``). The
arithmetic below works on plain tuples, so the tests drive it with
hand-built traces.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
ENVIRONMENT_PLANE = "Task Environment"  # its stat profile_start_time: Unix ns

# HLO opcodes that move data between chips
COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all",
)

# "%name.12 = <output type> opcode(" -- the output type may itself hold
# parentheses (tuples, tilings), so the opcode is the first lower-case word
# that follows a space and is followed by "("
_HLO = re.compile(r"^%?(?P<name>\S+) = (?P<out>.*?) (?P<op>[a-z][a-z0-9_\-]*)\(")
_ARRAY = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")

Event = tuple  # (start_ns, duration_ns, name)


@dataclasses.dataclass
class DeviceTrace:
    index: int
    ops: list[Event]
    async_ops: list[Event]
    modules: list[Event]


def find_xplane(trace_dir: str) -> str | None:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    return max(paths, key=os.path.getmtime) if paths else None


def read(path: str) -> tuple[list[DeviceTrace], int | None]:
    """``(devices, start_wall_ns)`` of one trace file: every accelerator
    plane as plain tuples, and the wall clock (Unix ns) at the trace's time 0
    where the file says it, which sets the trace beside the trainer's events."""
    from jax.profiler import ProfileData

    def events(line) -> list[Event]:
        return [(e.start_ns, e.duration_ns, e.name) for e in line.events]

    devices, start_wall_ns = [], None
    for plane in ProfileData.from_file(path).planes:
        if plane.name == ENVIRONMENT_PLANE:
            stats = dict(plane.stats)
            if "profile_start_time" in stats:
                start_wall_ns = int(stats["profile_start_time"])
            continue
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {line.name: events(line) for line in plane.lines}
        devices.append(DeviceTrace(
            index=int(m.group(1)),
            ops=lines.get(OPS_LINE, []),
            async_ops=lines.get(ASYNC_LINE, []),
            modules=lines.get(MODULES_LINE, []),
        ))
    devices.sort(key=lambda d: d.index)
    return devices, start_wall_ns


def last_seconds(
    devices: list[DeviceTrace], seconds: float
) -> tuple[list[DeviceTrace], tuple[float, float] | None]:
    """``(devices, window)``: the devices' events cut to the capture's last
    ``seconds``, and that window in trace ns. Starting the profiler stalls
    the host for up to a second, and the capture runs on while it stops; the
    end of it is the steady state that was asked for. Ops are clipped at the
    window's edge; of the programs only those that began inside it are kept,
    so that what is counted per step counts whole steps."""
    window = window_ns(devices)
    if window is None:
        return devices, None
    lo = max(window[0], window[1] - seconds * 1e9)

    def cut(events: list[Event]) -> list[Event]:
        return [
            (max(s, lo), s + d - max(s, lo), n) for s, d, n in events if s + d > lo
        ]

    return [
        DeviceTrace(
            d.index, cut(d.ops), cut(d.async_ops),
            [m for m in d.modules if m[0] >= lo],
        )
        for d in devices
    ], (lo, window[1])


def merged(events: list[Event]) -> list[tuple[float, float]]:
    """Union of the events' intervals, as sorted disjoint ``(start, end)``."""
    out: list[list[float]] = []
    for start, dur, _ in sorted(events, key=lambda e: e[0]):
        end = start + dur
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


def window_ns(devices: list[DeviceTrace]) -> tuple[float, float] | None:
    """From the first op to start to the last to end, over all devices."""
    spans = [(s, s + d) for dev in devices for s, d, _ in dev.ops]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def busy_ns(device: DeviceTrace) -> float:
    """Nanoseconds in which at least one op ran on the device."""
    return sum(e - s for s, e in merged(device.ops))


def busy_and_window_s(
    devices: list[DeviceTrace], window: tuple[float, float] | None = None
) -> tuple[float, float] | None:
    """``(busy_s, window_s)``: busy seconds averaged over the devices, and
    the length of the traced window (the one given, else from the first op
    to the last). ``None`` if no op ran on any device."""
    if window_ns(devices) is None:
        return None
    window = window or window_ns(devices)
    if window[1] <= window[0]:
        return None
    busy = sum(busy_ns(d) for d in devices) / len(devices)
    return busy / 1e9, (window[1] - window[0]) / 1e9


def idle_gaps(device: DeviceTrace, top: int = 5) -> list[tuple[float, float]]:
    """The longest idle gaps between ops on one device, longest first, as
    ``(start_ns, duration_ns)``."""
    spans = merged(device.ops)
    gaps = [(a[1], b[0] - a[1]) for a, b in zip(spans, spans[1:])]
    return sorted(gaps, key=lambda g: -g[1])[:top]


@functools.lru_cache(maxsize=65536)  # a step has ~3,000 distinct ops, run 20 times over
def parse_hlo(name: str) -> tuple[str, str, str]:
    """``(op name, opcode, largest output array)`` of an op event's name.
    A name that is not HLO text comes back whole, with nothing else."""
    m = _HLO.match(name)
    if not m:
        return name, "", ""
    arrays = _ARRAY.findall(m.group("out"))

    def size(a) -> int:
        n = 1
        for dim in filter(None, a[1].split(",")):
            n *= int(dim)
        return n

    largest = max(arrays, key=size, default=None)
    shape = f"{largest[0]}[{largest[1]}]" if largest else ""
    return m.group("name"), m.group("op"), shape


def op_table(device: DeviceTrace, top: int = 10) -> list[tuple[str, float]]:
    """Device seconds by kind of op, largest first. A kind is the op's name
    without its number, with its largest output array: every
    ``convert_reduce_fusion`` that writes ``bf16[2048,32,32,64]`` is one row,
    which for a ResNet is one row per stage and direction, not 3,000."""
    totals: dict[str, float] = {}
    for _, dur, name in device.ops:
        op_name, _, shape = parse_hlo(name)
        kind = re.sub(r"\.\d+$", "", op_name)
        key = f"{kind} {shape}".strip()
        totals[key] = totals.get(key, 0.0) + dur
    rows = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [(k, v / 1e9) for k, v in rows]


def step_module(device: DeviceTrace) -> str | None:
    """The program that took most device time in the capture: the step."""
    totals: dict[str, float] = {}
    for _, dur, name in device.modules:
        totals[name] = totals.get(name, 0.0) + dur
    return max(totals, key=totals.get) if totals else None


def is_collective(name: str) -> bool:
    op_name = parse_hlo(name)[0]
    return op_name.startswith(COLLECTIVES)


def collective_events(device: DeviceTrace) -> list[Event]:
    """The device's collectives, each once and for as long as it was in
    progress: synchronous ones from the ops line, asynchronous ones from
    their ``-start`` to their ``-done`` (the async line; the ``-start`` and
    ``-done`` ops themselves are left out of the first)."""
    sync = [
        e for e in device.ops
        if is_collective(e[2])
        and not parse_hlo(e[2])[0].rsplit(".", 1)[0].endswith(("-start", "-done"))
    ]
    return sync + [e for e in device.async_ops if is_collective(e[2])]


def collective_ms_per_step(device: DeviceTrace) -> float | None:
    """Milliseconds per step during which a collective was in progress on
    the device, total and not only what compute did not hide. Only whole
    steps count: collectives between the start of the first and the end of
    the last execution of the step program, over the number of those
    executions. ``None`` without a step."""
    module = step_module(device)
    if module is None:
        return None
    runs = [(s, s + d) for s, d, n in device.modules if n == module]
    lo, hi = min(s for s, _ in runs), max(e for _, e in runs)
    spans = [e for e in collective_events(device) if e[0] >= lo and e[0] + e[1] <= hi]
    return sum(d for _, d, _ in spans) / 1e6 / len(runs)
