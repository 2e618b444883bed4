"""The harness on the sequence encoder's toy cell, end to end on the CPU:
a sound run prints the contract's line with every compared number inside
its limit and the expert layers' counter among the per-layer metrics, and a
run with the trainer's step broken underneath (``bm_fault_run.py``) comes
out not correct by the reference's word. About a minute each."""

import json

import pytest

from bm_rehearsal import last_line
from bm_st_rehearsal import run_cell


def test_a_traced_rehearsal_agrees_with_the_reference_and_reads_the_counter():
    rc, lines, err = run_cell(trace=1, seconds=16.0)
    assert rc == 0, err[-2000:]
    result, notes = last_line(lines), json.loads(lines[-2])
    assert result["correct"] is False and "a rehearsal" in notes["why_not_correct"]
    assert not any("reference" in r for r in notes["why_not_correct"])
    assert set(result["compared"]) == {
        "loss", "grad_norm", "change_norm", "bn_stats", "no_window_share", "window_kernel"}
    for name, pair in result["compared"].items():
        assert 0 <= pair["error"] <= pair["limit"], name
    # no device plane on the CPU: the scopes' and rooflines' readers find
    # nothing and leave their metrics out; the program's counter is read
    assert {"boot_s", "compile_s", "expert_load_max_over_mean"} <= set(result["metrics"])
    assert not {"attention_ms", "moe_ms", "attention_roofline_pct", "experts_roofline_pct"} & set(result["metrics"])
    assert result["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
    assert not any("exit code" in r or "sha256" in r for r in notes["why_not_correct"])


@pytest.mark.parametrize("fault,over", [
    # nothing moved: every norm of a change reads 1 against the reference's
    ("unchanged", {"grad_norm", "change_norm"}),
    # 8 documents for 16: ln(2 x 8 - 1) for ln(2 x 16 - 1) on the loss
    ("half_batch", {"loss"}),
])
def test_a_broken_step_is_not_correct_by_the_references_word(fault, over):
    rc, lines, err = run_cell(trace=0, seconds=10.0, fault=fault)
    assert rc == 0, err[-2000:]
    result, notes = last_line(lines), json.loads(lines[-2])
    assert result["correct"] is False
    named = {r.split()[1] for r in notes["why_not_correct"] if r.startswith("reference: ")}
    assert over <= named, notes["why_not_correct"]
    for name in over:
        pair = result["compared"][name]
        assert pair["error"] > pair["limit"]
