"""The plain reference of ``smallthinker-21ba3b-enc``
(benchmarks/references/smallthinker_simclr.py) beside the program, on the
CPU at the toy cell's size: 16 documents a step in two rows of 64 tokens a
view, hidden 64, one period of four layers, 4 of 8 experts held.

With the program computing in float32 the two agree to rounding over three
training steps: each step's loss, the head's running statistics, the first
gradient as LARS hands it on and the parameters' change, leaf by leaf. The
reference put in the program's place one precision below the stated one (the
control), with half of a step's documents left out, with the state left
unchanged and with the window ignored (by the reference in the step, and by
the program's own attention asked for none) reads over the toy cell's limits. One process
(``smallthinker_calibrate.py``, through the trainer's entry point as a run
goes), about a minute. ``tests/test_sequence_encoder.py`` holds the layer's
parts (window, NoPE layer, router's place, renormalised weights) to the same
reference at weights drawn wide enough to show them.
"""

import os
import sys

import pytest

from bm_st_rehearsal import CELL, ROOT, calibrate

sys.path.insert(0, ROOT)

from benchmarks.run import load_json  # noqa: E402

# float32 against float32, read on the CPU: loss 2e-6, bn_stats 3e-6, worst
# leaf's norms 3e-5 / 2e-4 and turns 4e-5 / 3e-5, medians 1e-7 to 1e-6. A
# layer wired otherwise reads 1e-2 to 1.
TIGHT = {
    "loss": 5e-5, "bn_stats": 1e-4, "grad_norm": 2e-3, "change_norm": 5e-3,
    "grad_turn": 2e-3, "change_turn": 2e-3,
    "grad_turn_median": 1e-4, "change_turn_median": 1e-4,
    "grad_norm_median": 1e-5, "change_norm_median": 1e-4,
    # of the unwindowed reference's own step away from the reference's
    "no_window_share": 0.01,
    # of the program's attention alone (bfloat16 inputs, the toy row): 3e-4
    "window_kernel": 0.005,
}


@pytest.fixture(scope="module")
def readings():
    return calibrate(
        "--override", "precision.compute_dtype=float32", "--precisions", "float8",
        "--rows", "0.5", "--faults", "frozen,no_window",
    )


@pytest.mark.parametrize("number", sorted(TIGHT))
def test_the_block_stack_agrees_with_the_program_in_float32(readings, number):
    assert readings["program"][number] <= TIGHT[number]


@pytest.mark.parametrize("what,over", [
    ("float8", {"grad_norm", "change_norm"}),
    ("rows 0.5", {"loss", "bn_stats"}),
    ("frozen", {"grad_norm", "change_norm", "bn_stats"}),
    # the window ignored: under every distance's limit, all of its own
    # direction, in the step's gradient and in the program's attention alone
    ("no_window", {"no_window_share", "window_kernel"}),
])
def test_the_control_and_the_faults_read_over_the_toy_limits(readings, what, over):
    """What ``judge`` makes of the reference put in the program's place."""
    from benchmarks.references.smallthinker_simclr import judge

    limits = load_json("cells", CELL + ".json")["reference"]["limits"]
    beside, reasons = judge(readings[what], limits)
    named = {r.split()[1] for r in reasons}
    assert over <= named, reasons
    assert all(r.startswith("reference: ") for r in reasons)
