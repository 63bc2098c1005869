"""A traced run on the CPU at a tiny size, past the harness's look for a
chip: the profiler records the end of the window, and the host metrics
read only what came before it."""

import json

import pytest

from benchmark import run as R
from benchmark.tests.test_faults import tiny_cell

pytest.importorskip("lighthouse_tpu")


@pytest.mark.parametrize("trace_s", [None, 1.0])
def test_traced_run_reads_host_metrics_before_the_profiler(tiny, monkeypatch, capsys,
                                                           trace_s):
    monkeypatch.setattr(R, "find_devices", lambda chips: (
        {"platform": "cpu", "kind": "cpu", "count": 1}, None))
    cell = tiny_cell(tiny)
    cell.mix.pop("trace_s", None)
    if trace_s is not None:
        cell.mix["trace_s"] = trace_s
    traced = trace_s or R.TRACE_S
    cell.per_layer = [{"name": n, "unit": "u"}
                      for n in ("edge_post_ms", "queue_wait_ms", "device_calls_per_flush")]
    seen = {}
    real = R.context

    def spy(cell, seconds, setup_s, win, verdicts_ok):
        seen["win"] = win
        return real(cell, seconds, setup_s, win, verdicts_ok)

    monkeypatch.setattr(R, "context", spy)
    doc = R.run(cell, 6, 6.0, True)
    assert doc["correct"], doc["checks"]
    win = seen["win"]
    assert win.end - traced - 0.6 < win.host_end < win.end - traced
    assert win.host is not None
    assert any(r["post_start"] >= win.host_end for r in win.records)
    # the CPU trace has no device plane: the device readers find nothing
    assert set(doc["metrics"]) <= {"edge_post_ms", "queue_wait_ms"}
    assert "edge_post_ms" in doc["metrics"]
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines() if x.startswith("{")]
    loadgen = next(x["loadgen"] for x in lines if "loadgen" in x)
    assert loadgen["host_s"] == pytest.approx(6.0 - traced - 0.5, abs=0.1)
