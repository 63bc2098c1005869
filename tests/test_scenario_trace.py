"""Scenario harness, flight-recorder integration: the overlap gate,
trace dumps next to failing reports, and their determinism."""

import json

import pytest

from lighthouse_tpu.scenario import SCENARIOS, run_scenario
from lighthouse_tpu.scenario.slo import evaluate
from lighthouse_tpu.scenario.spec import DEFAULT_SLO

pytestmark = pytest.mark.scenario


# ---------------------------------------------------------------------------
# Flight-recorder integration: overlap gate, SLO-failure dumps, determinism
# ---------------------------------------------------------------------------


def test_overlap_gate_is_warn_level_and_never_flips_pass():
    # blown overlap ratio -> the gate reports not-ok at warn level, and a
    # run where it is the ONLY failure still counts as passing (the gate
    # is a telemetry tripwire, not a verdict)
    results = evaluate(
        {"max_overlap_wall_ratio": 1.5}, {},
        {"overlap_efficiency": {"ratio": 5.0, "mode": "pipeline"}},
    )
    (r,) = results
    assert r.name == "overlap_efficiency" and not r.ok
    assert r.level == "warn"
    assert r.to_dict()["level"] == "warn"
    assert all(x.ok for x in results if x.level == "fail")
    # a missing ratio (nothing to attribute) never fires the gate
    (r2,) = evaluate({"max_overlap_wall_ratio": 1.5}, {},
                     {"overlap_efficiency": {"ratio": None, "mode": "empty"}})
    assert r2.ok


def test_mainnet_shape_carries_overlap_slo():
    assert SCENARIOS["mainnet-shape"].slo["max_overlap_wall_ratio"] == 8.0
    assert DEFAULT_SLO["max_overlap_wall_ratio"] is None  # off by default


def _failing_smoke_spec(seed=None):
    from dataclasses import replace

    spec = SCENARIOS["smoke"]
    if seed is not None:
        spec = spec.with_seed(seed)
    # an unmeetable fail-level gate: the smoke run cannot detect 99
    # slashings (it runs no equivocation track)
    return replace(spec, slo={**spec.slo, "min_slashings_detected": 99})


def test_failing_run_leaves_trace_dump_next_to_report(tmp_path):
    out = tmp_path / "report.json"
    r = run_scenario(_failing_smoke_spec(), out_path=str(out))
    assert not r["pass"]
    assert r["trace_dump"] == str(out) + ".trace.json"
    doc = json.loads(open(r["trace_dump"]).read())
    names = {ev["name"] for ev in doc["traceEvents"]}
    assert "scenario.slot" in names
    # the dump is scoped to THIS run: every slot of the spec, no more
    slots = [ev for ev in doc["traceEvents"] if ev["name"] == "scenario.slot"]
    assert len(slots) == r["slots"]
    # the on-disk report references the artifact too
    assert json.loads(out.read_text())["trace_dump"] == r["trace_dump"]


def test_passing_run_has_no_trace_dump(tmp_path):
    out = tmp_path / "report.json"
    r = run_scenario("smoke", out_path=str(out))
    assert r["pass"]
    assert r["trace_dump"] is None
    assert not (tmp_path / "report.json.trace.json").exists()


def test_trace_dump_is_deterministic_under_fixed_seed(tmp_path):
    from collections import Counter

    spans = []
    for i in range(2):
        out = tmp_path / f"r{i}.json"
        r = run_scenario(_failing_smoke_spec(seed=77), out_path=str(out))
        doc = json.loads(open(r["trace_dump"]).read())
        spans.append(Counter(ev["name"] for ev in doc["traceEvents"]))
    # same seed => same work => the same span population, event for event
    assert spans[0] == spans[1]
    assert spans[0]["scenario.slot"] > 0
