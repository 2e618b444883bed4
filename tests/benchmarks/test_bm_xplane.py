"""The trace reduction (benchmarks/xplane.py) against hand-built traces whose
busy union, gaps and collective time are known, and against a real
``.xplane.pb`` written here byte by byte and read back through JAX."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks import xplane  # noqa: E402

FUSION = (
    "%convert_reduce_fusion.17 = (f32[64]{0:T(128)}, f32[64]{0:T(128)}, "
    "bf16[2048,32,32,64]{0,3,2,1:T(8,128)(2,1)}) fusion(f32[64]{0:T(128)S(1)} "
    "%copy-done.266, bf16[2048,32,32,64]{0,3,2,1:T(8,128)(2,1)} %fusion.3)"
)
FUSION_B = FUSION.replace("fusion.17", "fusion.18")
COPY = "%copy.4 = f32[64]{0:T(128)} copy(f32[64]{0:T(128)} %p.1)"
ALL_REDUCE = "%all-reduce.5 = f32[1024]{0:T(1024)} all-reduce(f32[1024]{0:T(1024)} %fusion.9), replica_groups={}"
AG_START = "%all-gather-start.2 = (bf16[2048,128]{1,0}, bf16[8192,128]{1,0}) all-gather-start(bf16[2048,128]{1,0} %fusion.7)"
AG_DONE = "%all-gather-done.2 = bf16[8192,128]{1,0} all-gather-done((bf16[2048,128]{1,0}, bf16[8192,128]{1,0}) %all-gather-start.2)"
STEP = "jit_local_step(4775550132771263068)"


def device(index=0, shift=0):
    """Two steps of 1,000 ns. In each: a fusion 0-400, a copy overlapping it
    300-500, a synchronous all-reduce 600-700, an all-gather in flight
    100-350 whose done op waits 340-350. Busy 0-500 and 600-700 = 600 ns a
    step; gaps of 100 (500-600) and 300 (700-1000)."""
    ops, async_ops, modules = [], [], []
    for k in range(2):
        t = shift + 1000 * k
        ops += [
            (t + 0, 400, FUSION if k == 0 else FUSION_B),
            (t + 300, 200, COPY),
            (t + 340, 10, AG_DONE),
            (t + 600, 100, ALL_REDUCE),
        ]
        async_ops.append((t + 100, 250, AG_START))
        modules.append((t + 0, 700, STEP))
        modules.append((t + 750, 10, "jit_convert_element_type(1)"))
    # a collective outside any whole step does not count
    ops.append((shift + 2100, 50, ALL_REDUCE))
    return xplane.DeviceTrace(index=index, ops=ops, async_ops=async_ops, modules=modules)


def test_busy_is_the_union_of_the_op_intervals():
    dev = device()
    assert xplane.merged(dev.ops)[:2] == [(0, 500), (600, 700)]
    assert xplane.busy_ns(dev) == 600 + 600 + 50


def test_window_spans_all_devices_and_busy_is_their_mean():
    a, b = device(0), device(1, shift=100)
    assert xplane.window_ns([a, b]) == (0, 2250)
    busy_s, window_s = xplane.busy_and_window_s([a, b])
    assert busy_s == pytest.approx(1250e-9)
    assert window_s == pytest.approx(2250e-9)


def test_no_ops_reduce_to_nothing():
    empty = xplane.DeviceTrace(index=0, ops=[], async_ops=[], modules=[])
    assert xplane.busy_and_window_s([empty]) is None
    assert xplane.busy_and_window_s([]) is None
    assert xplane.collective_ms_per_step(empty) is None
    assert xplane.idle_gaps(empty) == []


def test_idle_gaps_longest_first():
    # 500-600 and 700-1000 in the first step, 1500-1600 and 1700-2100 after
    assert [g[1] for g in xplane.idle_gaps(device(), top=5)] == [400, 300, 100, 100]
    assert xplane.idle_gaps(device(), top=2) == [(1700, 400), (700, 300)]
    assert xplane.idle_gaps(device(), top=1) == [(1700, 400)]


@pytest.mark.parametrize("name,expected", [
    (FUSION, ("convert_reduce_fusion.17", "fusion", "bf16[2048,32,32,64]")),
    (COPY, ("copy.4", "copy", "f32[64]")),
    (ALL_REDUCE, ("all-reduce.5", "all-reduce", "f32[1024]")),
    (AG_START, ("all-gather-start.2", "all-gather-start", "bf16[8192,128]")),
    ("not hlo at all", ("not hlo at all", "", "")),
])
def test_parse_hlo(name, expected):
    assert xplane.parse_hlo(name) == expected


def test_op_table_groups_by_kind_and_largest_output():
    table = dict(xplane.op_table(device()))
    # both fusions write the same array: one row
    assert table["convert_reduce_fusion bf16[2048,32,32,64]"] == pytest.approx(800e-9)
    assert table["copy f32[64]"] == pytest.approx(400e-9)
    assert xplane.op_table(device(), top=1)[0][0] == "convert_reduce_fusion bf16[2048,32,32,64]"


def test_collective_time_per_whole_step():
    dev = device()
    assert xplane.step_module(dev) == STEP
    # per step: all-reduce 100 ns + all-gather in flight 250 ns; the done
    # op's wait is inside that, and the all-reduce after the last step is not
    # in a whole step
    assert xplane.collective_ms_per_step(dev) == pytest.approx(350e-6)


def test_last_seconds_cuts_every_line_to_the_end_of_the_capture():
    # the capture ends at 2,150 ns; its last 650 ns begin at 1,500
    [cut], window = xplane.last_seconds([device()], 650e-9)
    assert window == (1500, 2150)
    # of the second step's fusion (1000-1400) and copy (1300-1500) nothing is
    # left; the idle 1500-1600 is at the window's edge and still counts
    assert xplane.busy_ns(cut) == 100 + 50
    busy_s, window_s = xplane.busy_and_window_s([cut], window)
    assert (busy_s, window_s) == (pytest.approx(150e-9), pytest.approx(650e-9))
    # the step that began at 1000 is not whole any more: only what began
    # inside the window is a program of it
    assert [m[2] for m in cut.modules] == ["jit_convert_element_type(1)"]
    assert xplane.last_seconds([], 1.0) == ([], None)
    # a capture shorter than what was asked for is taken whole
    assert xplane.last_seconds([device()], 1.0)[1] == (0, 2150)


# -- a real file ------------------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        n, low = n >> 7, n & 0x7F
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number: int, payload) -> bytes:
    """One protobuf field: an int as a varint, bytes/str length-delimited."""
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    data = payload.encode() if isinstance(payload, str) else payload
    return _varint(number << 3 | 2) + _varint(len(data)) + data


def _plane(name: str, lines: dict[str, list], stats: dict[str, int] | None = None) -> bytes:
    """XPlane{name=2, lines=3, event_metadata=4, stat_metadata=5, stats=6};
    XLine{name=2, events=4}; XEvent{metadata_id=1, offset_ps=2, duration_ps=3};
    XEventMetadata{id=1, name=2}; XStatMetadata{id=1, name=2};
    XStat{metadata_id=1, uint64_value=3} (tsl/profiler/protobuf/xplane.proto)."""
    ids: dict[str, int] = {}
    body = _field(2, name)
    for k, (stat_name, value) in enumerate((stats or {}).items(), start=1):
        body += _field(5, _field(1, k) + _field(2, _field(1, k) + _field(2, stat_name)))
        body += _field(6, _field(1, k) + _field(3, value))
    for line_name, events in lines.items():
        line = _field(2, line_name)
        for start_ns, dur_ns, event_name in events:
            meta = ids.setdefault(event_name, len(ids) + 1)
            line += _field(4, _field(1, meta) + _field(2, start_ns * 1000) + _field(3, dur_ns * 1000))
        body += _field(3, line)
    for event_name, meta in ids.items():
        body += _field(4, _field(1, meta) + _field(2, _field(1, meta) + _field(2, event_name)))
    return body


def test_read_finds_the_device_planes_of_a_real_file(tmp_path):
    dev = device()
    space = b"".join(_field(1, plane) for plane in [
        _plane("/device:TPU:1", {"XLA Ops": [(5, 7, COPY)]}),
        _plane("/device:TPU:0", {
            "XLA Ops": dev.ops, "Async XLA Ops": dev.async_ops,
            "XLA Modules": dev.modules, "Steps": [(0, 1, "0")],
        }),
        _plane("/host:CPU", {"main/1": [(42, 1000, "a host event")]}),
        _plane("Task Environment", {}, stats={
            "profile_start_time": 1790622058420505000,
            "profile_stop_time": 1790622063129444089,
        }),
    ])
    profile = tmp_path / "plugins" / "profile" / "2026_09_28" / "host.xplane.pb"
    profile.parent.mkdir(parents=True)
    profile.write_bytes(space)
    path = xplane.find_xplane(str(tmp_path))
    assert path == str(profile)
    devices, start_wall_ns = xplane.read(path)
    assert start_wall_ns == 1790622058420505000
    assert [d.index for d in devices] == [0, 1]
    assert sorted(devices[0].ops) == sorted((float(s), float(d), n) for s, d, n in dev.ops)
    assert len(devices[0].modules) == 4 and len(devices[0].async_ops) == 2
    assert devices[1].ops == [(5.0, 7.0, COPY)]
    assert xplane.collective_ms_per_step(devices[0]) == pytest.approx(350e-6)
    assert xplane.find_xplane(str(tmp_path / "plugins" / "profile" / "none")) is None
