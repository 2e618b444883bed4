"""The traced rehearsal, in a file of its own so that it runs beside the
other one: the capture thread, the trace's reading and the per-layer line.
The CPU backend has no device plane, so no device metric is read."""

import json

from bm_rehearsal import last_line, run_cell


def test_traced_rehearsal_reports_layer_metrics_and_no_device_metric():
    # the capture takes 4.5 of these seconds, and the epochs it touches are
    # left out of step_ms: the window has to hold some on either side
    rc, lines, err = run_cell("rehearsal-traced", trace=1, seconds=16.0)
    assert rc == 0, err[-2000:]
    result = last_line(lines)
    assert result["correct"] is False
    notes = json.loads(lines[-2])
    # step_ms only if whole epochs were left beside the capture: the CPU is shared
    step = {"step_ms"} if notes["measured"] else set()
    assert set(result["metrics"]) == {"boot_s", "compile_s"} | step
    # so: nothing from the CPU under a device metric's name (mfu_pct,
    # device_idle_pct, collective_ms)
    assert "busy_s" not in result["device"]
    assert notes["conductor_error"] is None
    assert "the trace shows no op on any device" in notes["why_not_correct"]
