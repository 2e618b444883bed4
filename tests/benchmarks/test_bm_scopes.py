"""Device time by scope (benchmarks/scopes.py and the six readers) against
a hand-built trace and a hand-written ``step_scopes.json``."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import scopes, xplane  # noqa: E402
from benchmarks.run import load_module  # noqa: E402

STEP = "jit_local_step(4775550132771263068)"
JIT = "jit(local_step)/"


def op(name: str, opcode: str = "fusion") -> str:
    return f"%{name} = f32[8]{{0:T(128)}} {opcode}(f32[8]{{0:T(128)}} %p.0)"


# instruction -> op_name, as the trainer writes it from the compiled program
SCOPES = {
    "fusion.1": JIT + "augment/vmap(jit(_uniform))/vmap()/add",
    "fusion.2": JIT + "jvp(encoder)/ContrastiveModel/f/conv_general_dilated",
    "fusion.3": JIT + "jvp(encoder)/ContrastiveModel/head/g/dot_general",
    "fusion.4": JIT + "jvp(ntxent)/all_gather",
    "fusion.5": JIT + "transpose(jvp(ntxent))/mul",
    "fusion.6": JIT + "transpose(jvp(encoder))/ContrastiveModel/head/g/dot_general",
    "convert_add_fusion.7": JIT + "transpose(jvp(encoder))/ContrastiveModel/f/conv_general_dilated",
    "all-reduce.8": JIT + "grad_reduce/psum",
    "fusion.9": JIT + "lars/mul",
    "fusion.10": JIT + "convert_element_type",  # in the step, under no scope
}
# name, duration in ns: 1,000 ns a step
STEP_OPS = [
    ("fusion.1", 50), ("fusion.2", 200), ("fusion.3", 20), ("fusion.4", 10),
    ("fusion.5", 15), ("fusion.6", 40), ("convert_add_fusion.7", 400),
    ("all-reduce.8", 30), ("fusion.9", 25), ("fusion.10", 5),
    ("copy-done.11", 5),  # the compiler's own prefetch: not in the map
]


def device(steps: int = 3) -> xplane.DeviceTrace:
    ops, modules = [], []
    for k in range(steps):
        t = 1000 * k
        modules.append((t, 900, STEP))
        for name, dur in STEP_OPS:
            kind = "all-reduce" if name.startswith("all-reduce") else "fusion"
            ops.append((t, dur, op(name, kind)))
            t += dur
        modules.append((1000 * k + 950, 10, "jit_add(1)"))
        ops.append((1000 * k + 950, 10, op("add.99", "add")))  # another program's
    # the capture ends inside a step: a third of an execution, with the ops
    # it got to, is not a step
    modules.append((1000 * steps, 300, STEP))
    ops.append((1000 * steps, 50, op("fusion.1")))
    ops.append((1000 * steps + 50, 200, op("fusion.2")))
    return xplane.DeviceTrace(index=0, ops=ops, async_ops=[], modules=modules)


def run_record(tmp_path, scope_map=SCOPES, dev=None) -> dict:
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir, exist_ok=True)
    if scope_map is not None:
        with open(os.path.join(run_dir, "step_scopes.json"), "w") as f:
            json.dump(scope_map, f)
    dev = dev or device()
    return {
        "cell": {"name": "r18-lb8192-4chip"}, "run_dir": run_dir,
        "trace": {"devices": [dev], "window_ns": xplane.window_ns([dev]),
                  "start_wall_ns": None},
    }


def reader(name: str):
    return load_module("layer_metrics", name + ".py").read


@pytest.mark.parametrize("op_name,part", [
    (JIT + "augment/vmap()/sub", "augment"),
    (JIT + "jvp(encoder)/f/BatchNorm_0/reduce_sum", "fwd"),
    (JIT + "transpose(jvp(encoder))/f/BatchNorm_0/mul", "bwd"),
    (JIT + "jvp(encoder)/ContrastiveModel/head/g/Dense_0/dot_general", "fwd"),
    (JIT + "transpose(jvp(encoder))/ContrastiveModel/head/g/Dense_0/transpose", "bwd"),
    (JIT + "transpose(jvp(ntxent))/dot_general", "loss"),  # both directions
    (JIT + "grad_reduce/psum", "grad_reduce"),
    (JIT + "lars/sqrt", "optimizer"),
    (JIT + "convert_element_type", "unscoped"),
    ("jit(local_step)/encoder_stats/mul", "unscoped"),  # a name is a whole path part
    (None, "unscoped"),
])
def test_an_op_name_resolves_to_its_innermost_scope(op_name, part):
    assert scopes.part_of(op_name) == part


def test_parts_per_step_add_up_to_the_busy_time_of_whole_steps(tmp_path):
    parts = scopes.ms_per_step(device(), SCOPES)
    assert parts == pytest.approx({
        "augment": 50e-6, "fwd": 220e-6, "bwd": 440e-6, "loss": 25e-6,
        "grad_reduce": 30e-6, "optimizer": 25e-6,
        # the unmapped copy-done and the op under no scope in each step;
        # another program's add between two steps is no part of a step
        "unscoped": 10e-6,
    })
    assert sum(parts.values()) == pytest.approx(800e-6)
    assert len(scopes.whole_steps(device())) == 3


@pytest.mark.parametrize("metric,ns", [
    ("augment_ms", 50), ("fwd_ms", 220), ("bwd_ms", 440), ("loss_ms", 25),
    ("optimizer_ms", 25), ("grad_reduce_ms", 30),
])
def test_each_reader_reads_its_part(tmp_path, metric, ns):
    assert reader(metric)(run_record(tmp_path)) == pytest.approx(ns * 1e-6)


@pytest.mark.parametrize("metric", [
    "augment_ms", "fwd_ms", "bwd_ms", "loss_ms", "optimizer_ms", "grad_reduce_ms",
])
def test_none_without_the_map_without_a_trace_or_without_a_step(tmp_path, metric):
    read = reader(metric)
    # the parent commit writes no step_scopes.json
    assert read(run_record(tmp_path, scope_map=None)) is None
    # an untraced run, and a CPU trace with no device plane
    assert read({**run_record(tmp_path), "trace": None}) is None
    empty = run_record(tmp_path)
    empty["trace"]["devices"] = []
    assert read(empty) is None
    # a trace whose device ran no program
    idle = xplane.DeviceTrace(index=0, ops=[], async_ops=[], modules=[])
    assert read(run_record(tmp_path, dev=idle)) is None
    # a file that is not a map
    broken = run_record(tmp_path)
    with open(os.path.join(broken["run_dir"], "step_scopes.json"), "w") as f:
        f.write("[1, 2")
    assert read(broken) is None


def test_a_part_that_never_ran_is_left_out(tmp_path):
    """One chip reduces no gradient: no op resolves to ``grad_reduce`` and
    the reader says None, not 0."""
    one_chip = {k: v for k, v in SCOPES.items() if "grad_reduce" not in v}
    run = run_record(tmp_path, scope_map=one_chip)
    assert reader("grad_reduce_ms")(run) is None
    assert reader("bwd_ms")(run) == pytest.approx(440e-6)
