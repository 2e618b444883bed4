"""End to end on the CPU: the command on the toy cell prints the contract's
last line, never correct; on a cell of the benchmark it refuses the CPU.
About 35 s: ResNet-18 compiles here, once."""

import json

from bm_rehearsal import last_line, run_cell


def test_rehearsal_prints_the_contracts_line_and_is_never_correct():
    rc, lines, err = run_cell("rehearsal", trace=0, seconds=16.0)
    assert rc == 0, err[-2000:]
    result = last_line(lines)
    assert result["correct"] is False
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == 1
    notes = json.loads(lines[-2])
    # a two-step epoch takes about a second here, but this test shares its
    # CPU with five others: the rate is asked for only if the window held it
    rate = {"imgs_per_s_per_chip"} if notes["measured"] else set()
    assert set(result["metrics"]) == {"preempt_s", "setup_s"} | rate
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "a rehearsal" in notes["why_not_correct"]
    assert any("platform" in r for r in notes["why_not_correct"])
    # the trainer left as a preempted run does, with a checkpoint that verified
    assert not any("exit code" in r or "sha256" in r for r in notes["why_not_correct"])


def test_without_an_accelerator_a_cell_prints_nothing_and_fails():
    rc, lines, err = run_cell("r18-b2048-1chip", trace=0, rehearse=False)
    assert rc != 0
    assert lines == []
    assert "no accelerator" in err
