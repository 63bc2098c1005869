"""Scenario harness suite: spec registry, SLO gating, and the SLO-gated
multi-node runs themselves.

The fast tier runs the ``smoke`` scenario (3 nodes, 2 epochs, one fault
track) twice to pin seed-determinism; the flagship ``mainnet-shape`` run
and its breaker-disabled degraded twin are marked ``slow`` (they are the
acceptance soaks ``tools/scenario_run.py`` drives in CI's long lane).
The flight-recorder tests are in test_scenario_trace.py and the
saturation regimes in test_scenario_saturation.py, so that a parallel
run spreads the scenario runs over workers.
"""

import json

import pytest

from lighthouse_tpu.scenario import (
    SCENARIOS,
    ScenarioSpec,
    parse_scenario_arg,
    run_scenario,
)
from lighthouse_tpu.scenario.adversity import build_tracks
from lighthouse_tpu.scenario.slo import evaluate
from lighthouse_tpu.scenario.spec import DEFAULT_SLO
from lighthouse_tpu.scenario.traffic import build_shapes

pytestmark = pytest.mark.scenario


# ---------------------------------------------------------------------------
# Spec registry + parsing
# ---------------------------------------------------------------------------


class TestSpecs:
    def test_registry_names_and_thresholds(self):
        assert {"smoke", "mainnet-shape", "mainnet-shape-degraded"} <= set(
            SCENARIOS
        )
        for spec in SCENARIOS.values():
            merged = spec.slo_thresholds()
            assert set(merged) == set(DEFAULT_SLO) | set(spec.slo)
            # every override key must be a known gate
            assert set(spec.slo) <= set(DEFAULT_SLO)

    def test_parse_scenario_arg(self):
        spec = parse_scenario_arg("smoke")
        assert spec.name == "smoke" and spec.seed == 1234
        spec = parse_scenario_arg("mainnet-shape:seed=99")
        assert spec.name == "mainnet-shape" and spec.seed == 99
        with pytest.raises(ValueError):
            parse_scenario_arg("no-such-scenario")
        with pytest.raises(ValueError):
            parse_scenario_arg("smoke:frobnicate=1")

    def test_unknown_shape_and_track_rejected(self):
        with pytest.raises(ValueError):
            build_shapes(("no-such-shape",))
        with pytest.raises(ValueError):
            build_tracks(("no-such-track:x=1",))

    def test_every_registered_spec_builds(self):
        for spec in SCENARIOS.values():
            assert build_shapes(spec.traffic) is not None
            assert build_tracks(spec.adversity) is not None


# ---------------------------------------------------------------------------
# SLO evaluation semantics (pure, no nodes)
# ---------------------------------------------------------------------------


def _deltas(**over):
    base = {
        "processor_shed_total": 0.0,
        "sync_stalls_total": 0.0,
        "breaker_transitions_total": 0.0,
        "verify_device_retries_total": 0.0,
        "faults_injected_total": 0.0,
        "import_p99_s": 0.1,
        "verify_p99_s": 0.1,
    }
    base.update(over)
    return base


def _run(**over):
    base = {
        "processor_enqueues": 100,
        "heads": ["aa", "aa"],
        "finalized_epochs": [2, 2],
        "never_raise_violations": 0,
        "breaker_closed": True,
        "crash_reports": [{"ok": True}],
        "slashings_detected": 0,
    }
    base.update(over)
    return base


class TestSLOEvaluate:
    def test_all_green(self):
        results = evaluate(dict(DEFAULT_SLO), _deltas(), _run())
        assert results and all(r.ok for r in results)

    def test_none_threshold_disables_gate(self):
        t = dict(DEFAULT_SLO)
        t["max_sync_stalls"] = None
        results = evaluate(t, _deltas(sync_stalls_total=99.0), _run())
        assert "sync_stalls" not in {r.name for r in results}

    def test_max_gates_fail_above_threshold(self):
        results = evaluate(
            dict(DEFAULT_SLO),
            _deltas(verify_device_retries_total=17.0),
            _run(),
        )
        by_name = {r.name: r for r in results}
        assert not by_name["device_retries"].ok

    def test_min_gates_fail_below_threshold(self):
        t = dict(DEFAULT_SLO)
        t["min_breaker_transitions"] = 1
        t["min_slashings_detected"] = 1
        results = evaluate(t, _deltas(), _run())
        by_name = {r.name: r for r in results}
        assert not by_name["breaker_engaged"].ok
        assert not by_name["slashings_detected"].ok

    def test_divergent_heads_and_crash_failure(self):
        results = evaluate(
            dict(DEFAULT_SLO),
            _deltas(),
            _run(heads=["aa", "bb"], crash_reports=[{"ok": False}]),
        )
        by_name = {r.name: r for r in results}
        assert not by_name["head_convergence"].ok
        assert not by_name["crash_recovery"].ok

    def test_shed_rate_is_a_rate(self):
        results = evaluate(
            dict(DEFAULT_SLO),
            _deltas(processor_shed_total=60.0),
            _run(processor_enqueues=100),
        )
        by_name = {r.name: r for r in results}
        assert not by_name["shed_rate"].ok
        assert by_name["shed_rate"].observed == pytest.approx(0.6)


# ---------------------------------------------------------------------------
# The smoke scenario: tier-1 budget, run twice for determinism
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_report():
    """One canonical ``smoke`` run (about 20 s), read by every test here
    that needs the canonical run rather than a run of its own."""
    return run_scenario("smoke")


def test_smoke_scenario_passes_and_is_deterministic(tmp_path, smoke_report):
    out = tmp_path / "report.json"
    hist = tmp_path / "history.jsonl"
    r1 = run_scenario("smoke", out_path=str(out), history_path=str(hist))
    r2 = smoke_report
    assert r1["pass"], [s for s in r1["slo"] if not s["ok"]]
    assert r2["pass"]
    # exact reproducibility: same seed => same fault sequence, same heads
    assert r1["fingerprint"] == r2["fingerprint"]
    assert r1["fired_faults"] == r2["fired_faults"]
    assert len(r1["fired_faults"]) > 0, "the fault track must have bitten"
    # the JSON report round-trips and carries the reproduction seed
    on_disk = json.loads(out.read_text())
    assert on_disk["seed"] == SCENARIOS["smoke"].seed
    assert on_disk["fingerprint"] == r1["fingerprint"]
    assert [tuple(f) for f in on_disk["fired_faults"]] == [
        tuple(f) for f in r1["fired_faults"]
    ]
    # one BENCH_HISTORY scenario row
    rows = [json.loads(ln) for ln in hist.read_text().splitlines()]
    assert len(rows) == 1 and rows[0]["kind"] == "scenario"
    assert rows[0]["pass"] and rows[0]["fingerprint"] == r1["fingerprint"]
    # per-SLO warn levels ride along in the JSON report
    assert all("level" in s for s in on_disk["slo"])
    assert "slo_warnings" in on_disk


def test_seed_override_changes_the_run(smoke_report):
    spec = SCENARIOS["smoke"].with_seed(4321)
    assert isinstance(spec, ScenarioSpec) and spec.seed == 4321
    r = run_scenario(spec)
    # a different seed draws a different fault stream; the run still
    # reports honestly either way (pass is not asserted here — only
    # that the fingerprint diverges from the canonical seed's)
    assert r["fingerprint"] != smoke_report["fingerprint"]


def test_smoke_run_reports_overlap_facts(smoke_report):
    ov = smoke_report["facts"]["overlap_efficiency"]
    assert ov["mode"] in ("pipeline", "serial", "empty")
    if ov["ratio"] is not None:
        assert ov["ratio"] > 0


# ---------------------------------------------------------------------------
# The flagship: every shape + every track at once (slow lane)
# ---------------------------------------------------------------------------


# Pinned pre-refactor value: the shared-genesis-fixture refactor (one
# cached interop state, copy-on-write per node) and the big-registry
# serialization caches must not change what the flagship run computes.
# If an intentional engine change moves it, re-pin deliberately.
MAINNET_SHAPE_FINGERPRINT = "e623de0a8e7926f0"


@pytest.mark.slow
def test_mainnet_shape_passes_all_slos_twice():
    r1 = run_scenario("mainnet-shape")
    r2 = run_scenario("mainnet-shape")
    assert r1["pass"], [s for s in r1["slo"] if not s["ok"]]
    assert r2["pass"]
    assert r1["fingerprint"] == r2["fingerprint"]
    assert r1["fingerprint"] == MAINNET_SHAPE_FINGERPRINT
    by_name = {s["name"]: s for s in r1["slo"]}
    # the adversity actually bit: breaker engaged, slasher caught the
    # equivocation, the kill -9 iteration recovered
    assert by_name["breaker_engaged"]["ok"]
    assert by_name["slashings_detected"]["observed"] >= 1
    assert by_name["crash_recovery"]["ok"]
    assert by_name["finalization"]["observed"] >= 1
    assert r1["facts"]["deposits_applied"] >= 1
    assert r1["facts"]["gossip_deliveries_dropped"] >= 1
    assert r1["facts"].get("byzantine_heals", 0) >= 0


@pytest.mark.slow
def test_mainnet_shape_degraded_fails_loudly():
    r = run_scenario("mainnet-shape-degraded")
    assert not r["pass"], "a disabled breaker must blow at least one SLO"
    failed = [s["name"] for s in r["slo"] if not s["ok"]]
    assert "device_retries" in failed, failed


# ---------------------------------------------------------------------------
# Hostile regimes (ROADMAP item 5): long non-finality, slashing/exit
# flood, checkpoint sync through byzantine peers, cheap-node registry
# pressure
# ---------------------------------------------------------------------------


def test_long_non_finality_regime():
    """Multi-epoch finality stall: attestation suppression pins finality
    at genesis while the pool-growth and shuffling-cache-pressure gates
    prove nothing leaks while the chain can't finalize."""
    r = run_scenario("long-non-finality")
    assert r["pass"], [s for s in r["slo"] if not s["ok"]]
    by_name = {s["name"]: s for s in r["slo"]}
    assert by_name["finality_stalled"]["observed"] == 0
    assert by_name["op_pool_growth"]["ok"]
    assert by_name["shuffling_cache_pressure"]["observed"] <= 16
    assert r["facts"]["attestations_suppressed"] > 0


def test_registry_pressure_cheap_nodes():
    """The cheap-node acceptance path: 12 in-process nodes over a
    100k-entry validator registry (16 interop keys + copy-on-write
    frozen padding) complete an epoch inside the fast-tier budget."""
    spec = SCENARIOS["registry-pressure"]
    assert spec.n_nodes >= 12 and spec.registry_padding >= 99_000
    r = run_scenario("registry-pressure")
    assert r["pass"], [s for s in r["slo"] if not s["ok"]]
    assert r["nodes"] == spec.n_nodes
    assert len(set(r["facts"]["heads"])) == 1, "nodes must converge"


@pytest.mark.slow
def test_slashing_flood_regime_deterministic():
    r1 = run_scenario("slashing-flood")
    r2 = run_scenario("slashing-flood")
    assert r1["pass"], [s for s in r1["slo"] if not s["ok"]]
    assert r1["fingerprint"] == r2["fingerprint"]
    by_name = {s["name"]: s for s in r1["slo"]}
    assert by_name["slashings_detected"]["observed"] >= 2
    assert by_name["exits_processed"]["observed"] >= 6


@pytest.mark.slow
def test_hostile_checkpoint_sync_regime_deterministic():
    r1 = run_scenario("hostile-checkpoint-sync")
    r2 = run_scenario("hostile-checkpoint-sync")
    assert r1["pass"], [s for s in r1["slo"] if not s["ok"]]
    assert r1["fingerprint"] == r2["fingerprint"]
    by_name = {s["name"]: s for s in r1["slo"]}
    # the checkpoint-synced node converged on the honest head and the
    # peer scorer banned every byzantine server
    assert by_name["checkpoint_convergence"]["ok"]
    assert by_name["hostile_peers_banned"]["observed"] >= 2
    # the all-hostile phase must stall exactly once (the honest peer
    # re-arms sync afterwards); a clean pass here proves the ladder
    assert by_name["sync_stalls"]["observed"] == 1


@pytest.mark.slow
def test_long_non_finality_regime_deterministic():
    r1 = run_scenario("long-non-finality")
    r2 = run_scenario("long-non-finality")
    assert r1["pass"] and r2["pass"]
    assert r1["fingerprint"] == r2["fingerprint"]


# ---------------------------------------------------------------------------
# tools/scenario_run.py --repeat: the one-flag determinism gate
# ---------------------------------------------------------------------------


def _load_scenario_run_tool():
    import importlib.util
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "scenario_run_tool", os.path.join(root, "tools", "scenario_run.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _StubEngine:
    """Stands in for ScenarioEngine: returns queued fingerprints so the
    --repeat divergence logic is testable in milliseconds."""

    queue: list = []

    def __init__(self, spec, out_path=None, history_path=None):
        self.spec = spec

    def run(self):
        fp = type(self).queue.pop(0)
        return {
            "scenario": self.spec.name, "seed": self.spec.seed,
            "pass": True, "fingerprint": fp, "slots": 16,
            "fired_faults": [], "elapsed_s": 0.0, "slo": [],
            "slo_warnings": [], "trace_dump": None,
        }


class TestScenarioRunRepeat:
    def test_stable_fingerprints_exit_zero(self, monkeypatch, capsys):
        import lighthouse_tpu.scenario.engine as engine_mod

        tool = _load_scenario_run_tool()
        _StubEngine.queue = ["aaaa", "aaaa", "aaaa"]
        monkeypatch.setattr(engine_mod, "ScenarioEngine", _StubEngine)
        rc = tool.main(["--scenario", "smoke", "--repeat", "3",
                        "--no-history"])
        assert rc == 0
        assert "fingerprint stable over 3 runs" in capsys.readouterr().out

    def test_divergent_fingerprints_exit_two(self, monkeypatch, capsys):
        import lighthouse_tpu.scenario.engine as engine_mod

        tool = _load_scenario_run_tool()
        _StubEngine.queue = ["aaaa", "bbbb"]
        monkeypatch.setattr(engine_mod, "ScenarioEngine", _StubEngine)
        rc = tool.main(["--scenario", "smoke", "--repeat", "2",
                        "--no-history"])
        assert rc == 2
        assert "FINGERPRINT DIVERGENCE" in capsys.readouterr().out

    def test_repeat_must_be_positive(self):
        tool = _load_scenario_run_tool()
        with pytest.raises(SystemExit):
            tool.main(["--scenario", "smoke", "--repeat", "0",
                       "--no-history"])


# ---------------------------------------------------------------------------
# CLI entry
# ---------------------------------------------------------------------------


def test_bn_scenario_unknown_name_errors_fast():
    from lighthouse_tpu import cli

    with pytest.raises(ValueError):
        cli.main(["--spec", "minimal", "bn", "--scenario", "no-such"])


def test_bn_scenario_smoke_exits_zero():
    from lighthouse_tpu import cli

    assert cli.main(["--spec", "minimal", "bn", "--scenario", "smoke"]) == 0


# ---------------------------------------------------------------------------
# tools/scenario_run.py --repeat: per-epoch SLO snapshot diffing
# ---------------------------------------------------------------------------


class _EpochStubEngine(_StubEngine):
    """Queues (fingerprint, epochs) pairs: stable fingerprints with
    divergent per-epoch snapshots is exactly the drift the epoch diff
    exists to catch (the fingerprint never covers snapshot facts)."""

    queue: list = []

    def run(self):
        fp, epochs = type(self).queue.pop(0)
        return {
            "scenario": self.spec.name, "seed": self.spec.seed,
            "pass": True, "fingerprint": fp, "slots": 16,
            "fired_faults": [], "elapsed_s": 0.0, "slo": [],
            "slo_warnings": [], "trace_dump": None, "epochs": epochs,
        }


def _epoch_rec(epoch, ok=True, depth=1):
    return {"epoch": epoch, "facts": {"deposit_queue_depth": depth},
            "slo": [{"name": "deposit_queue_depth", "ok": ok}]}


class TestScenarioRunEpochDiff:
    def test_divergent_epoch_snapshots_exit_two(self, monkeypatch, capsys):
        import lighthouse_tpu.scenario.engine as engine_mod

        tool = _load_scenario_run_tool()
        _EpochStubEngine.queue = [
            ("aaaa", [_epoch_rec(1), _epoch_rec(2, ok=True)]),
            ("aaaa", [_epoch_rec(1), _epoch_rec(2, ok=False)]),
        ]
        monkeypatch.setattr(engine_mod, "ScenarioEngine", _EpochStubEngine)
        rc = tool.main(["--scenario", "smoke", "--repeat", "2",
                        "--no-history"])
        assert rc == 2
        out = capsys.readouterr().out
        assert "EPOCH SLO DIVERGENCE" in out
        assert "first divergent epoch 2" in out

    def test_divergent_facts_name_the_first_epoch(self, monkeypatch,
                                                  capsys):
        import lighthouse_tpu.scenario.engine as engine_mod

        tool = _load_scenario_run_tool()
        _EpochStubEngine.queue = [
            ("aaaa", [_epoch_rec(1, depth=3), _epoch_rec(2, depth=9)]),
            ("aaaa", [_epoch_rec(1, depth=4), _epoch_rec(2, depth=9)]),
        ]
        monkeypatch.setattr(engine_mod, "ScenarioEngine", _EpochStubEngine)
        rc = tool.main(["--scenario", "smoke", "--repeat", "2",
                        "--no-history"])
        assert rc == 2
        assert "first divergent epoch 1" in capsys.readouterr().out

    def test_missing_epoch_records_tolerated(self, monkeypatch, capsys):
        # older engines / stub reports carry no "epochs" key: the diff
        # must treat them as empty, not crash
        import lighthouse_tpu.scenario.engine as engine_mod

        tool = _load_scenario_run_tool()
        _StubEngine.queue = ["cccc", "cccc"]
        monkeypatch.setattr(engine_mod, "ScenarioEngine", _StubEngine)
        rc = tool.main(["--scenario", "smoke", "--repeat", "2",
                        "--no-history"])
        assert rc == 0
        assert "fingerprint stable over 2 runs" in capsys.readouterr().out

    def test_stable_epoch_snapshots_reported(self, monkeypatch, capsys):
        import lighthouse_tpu.scenario.engine as engine_mod

        tool = _load_scenario_run_tool()
        recs = [_epoch_rec(1), _epoch_rec(2)]
        _EpochStubEngine.queue = [("dddd", recs), ("dddd", recs)]
        monkeypatch.setattr(engine_mod, "ScenarioEngine", _EpochStubEngine)
        rc = tool.main(["--scenario", "smoke", "--repeat", "2",
                        "--no-history"])
        assert rc == 0
        assert "per-epoch SLO snapshots stable over 2 runs" in \
            capsys.readouterr().out
