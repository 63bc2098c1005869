"""Scenario harness, saturation regimes: deposit-queue saturation,
adversarial aggregation storms, the per-epoch SLO snapshots behind
first_violation_epoch, the committed regression corpus, and the
1M-validator soak (slow)."""

import pytest

from lighthouse_tpu.scenario import SCENARIOS, parse_scenario_arg, run_scenario
from lighthouse_tpu.scenario.slo import EPOCH_GATED_KEYS, evaluate_epoch
from lighthouse_tpu.scenario.spec import DEFAULT_SLO

pytestmark = pytest.mark.scenario


# ---------------------------------------------------------------------------
# Saturation soaks (ROADMAP item 5 follow-through): deposit-queue
# saturation, adversarial aggregation storms, and the per-epoch SLO
# snapshot machinery behind first_violation_epoch
# ---------------------------------------------------------------------------

class TestEvaluateEpoch:
    def test_epoch_gates_localize_the_three_soak_keys(self):
        t = {"max_deposit_queue_depth": 10, "max_ssz_cache_bytes": 100,
             "max_pool_estimated_verify_cost": 5}
        results = evaluate_epoch(t, {
            "deposit_queue_depth": 11, "ssz_cache_bytes": 50,
            "pool_estimated_verify_cost": 5,
        })
        by_name = {r.name: r for r in results}
        assert set(by_name) == {
            "deposit_queue_depth", "ssz_cache_bytes", "pool_verify_cost"
        }
        assert not by_name["deposit_queue_depth"].ok
        assert by_name["ssz_cache_bytes"].ok
        assert by_name["pool_verify_cost"].ok  # at the limit is ok

    def test_none_thresholds_produce_no_epoch_gates(self):
        assert evaluate_epoch(dict(DEFAULT_SLO), {}) == []

    def test_epoch_gated_keys_are_registered_thresholds(self):
        assert set(EPOCH_GATED_KEYS) <= set(DEFAULT_SLO)


# Pinned fingerprints for the saturation regimes.  The healthy and
# weakened-drain deposit twins share traffic but not spec overrides, so
# their fingerprints differ; the two storm twins differ only in the
# serve admission cost model, which the fingerprint inputs (faults,
# heads, finality) never see — identical fingerprints there prove the
# admission knob is out of the consensus path.
DEPOSIT_SATURATION_FINGERPRINT = "e25e57e52ab17be5"
DEPOSIT_SATURATION_LAGGING_FINGERPRINT = "78eae5d1d5516fae"
AGGREGATION_STORM_FINGERPRINT = "e5fb384b9a2bef1c"


def test_deposit_saturation_drain_keeps_pace():
    r = run_scenario("deposit-saturation")
    assert r["pass"], [s for s in r["slo"] if not s["ok"]]
    assert r["fingerprint"] == DEPOSIT_SATURATION_FINGERPRINT
    assert r["first_violation_epoch"] is None
    by_name = {s["name"]: s for s in r["slo"]}
    # inflow really outran the drain (backlog grew) yet stayed in budget
    assert 0 < by_name["deposit_queue_depth"]["observed"] <= 64
    assert by_name["deposit_drain"]["observed"] >= 48
    assert r["facts"]["deposits_queued"] > r["facts"]["deposits_applied"]
    # per-epoch snapshots rode along, one per epoch, each with the
    # epoch-localized gate verdicts
    assert len(r["epochs"]) == SCENARIOS["deposit-saturation"].epochs
    for rec in r["epochs"]:
        assert {"epoch", "metrics", "facts", "slo"} <= set(rec)
        assert "deposit_queue_depth" in rec["facts"]


def test_deposit_saturation_lagging_fails_at_the_epoch_it_starts():
    r = run_scenario("deposit-saturation-lagging")
    assert not r["pass"]
    assert r["fingerprint"] == DEPOSIT_SATURATION_LAGGING_FINGERPRINT
    failed = [s["name"] for s in r["slo"] if not s["ok"]]
    assert "deposit_queue_depth" in failed, failed
    # the backlog first crosses the 64-deposit budget at epoch 3 (depths
    # 2/25/65/105) — the report must localize the violation there
    assert r["first_violation_epoch"] == 3
    epoch3 = [e for e in r["epochs"] if e["epoch"] == 3][0]
    bad = [g for g in epoch3["slo"] if not g["ok"]]
    assert any(g["name"] == "deposit_queue_depth" for g in bad)


def test_aggregation_storm_cost_model_sheds_the_overage():
    r = run_scenario("aggregation-storm")
    assert r["pass"], [s for s in r["slo"] if not s["ok"]]
    assert r["fingerprint"] == AGGREGATION_STORM_FINGERPRINT
    by_name = {s["name"]: s for s in r["slo"]}
    # cost-priced admission shed the storm's near-duplicate overage...
    assert by_name["storm_shed"]["observed"] >= 0.5
    assert by_name["naive_pool_growth"]["ok"]
    assert by_name["pool_verify_cost"]["ok"]
    # ...without touching the honest tenant's deadlines
    assert by_name["honest_deadline_misses"]["observed"] <= 0.02
    assert r["facts"]["storm_admitted"] < r["facts"]["storm_submitted"]


def test_aggregation_storm_uncosted_twin_fails_the_overload_gate():
    r = run_scenario("aggregation-storm-uncosted")
    assert not r["pass"]
    # set-count admission admits everything: same consensus history
    # (identical fingerprint), blown pool gates
    assert r["fingerprint"] == AGGREGATION_STORM_FINGERPRINT
    failed = [s["name"] for s in r["slo"] if not s["ok"]]
    assert "naive_pool_growth" in failed and "pool_verify_cost" in failed
    # uncosted pool cost crosses the 1024 budget at epoch 2 (504/1080/1656)
    assert r["first_violation_epoch"] == 2


# ---------------------------------------------------------------------------
# The committed regression corpus: search findings replay standalone
# ---------------------------------------------------------------------------

REGRESS_FIXTURE = "regress-deposit_queue_depth-deposit_drain-586964"


def test_committed_fixture_resolves_through_parse_scenario_arg():
    spec = parse_scenario_arg(REGRESS_FIXTURE)
    assert spec.name == REGRESS_FIXTURE and spec.seed == 586964
    # overrides compose with fixture resolution like registry names
    assert parse_scenario_arg(f"{REGRESS_FIXTURE}:seed=5").seed == 5


def test_committed_fixture_replays_its_violation_standalone():
    r = run_scenario(parse_scenario_arg(REGRESS_FIXTURE))
    assert not r["pass"]
    assert r["fingerprint"] == "a606c5b6dfbc2284"
    failed = [s["name"] for s in r["slo"] if not s["ok"]]
    assert failed == ["deposit_drain"]


def test_fixture_round_trip_and_validation():
    from lighthouse_tpu.scenario.spec import spec_from_json, spec_to_json

    spec = SCENARIOS["deposit-saturation"]
    assert spec_from_json(spec_to_json(spec)) == spec
    with pytest.raises(ValueError, match="unknown scenario fixture field"):
        spec_from_json({"name": "x", "seed": 1, "frobnicate": 2})
    with pytest.raises(ValueError, match="missing 'seed'"):
        spec_from_json({"name": "x"})
    with pytest.raises(ValueError, match="unregistered SLO"):
        spec_from_json({"name": "x", "seed": 1, "slo": {"max_bogus": 1}})


# ---------------------------------------------------------------------------
# The 1M-validator multi-epoch soak (slow tier, `pytest -m soak`):
# registry-pressure's copy-on-write trick stretched 10x, with the SSZ
# byte budget as a hard per-epoch SLO and a host peak-memory pin.
# ---------------------------------------------------------------------------


SOAK_1M_FINGERPRINT = "60080233cf7934a2"


@pytest.mark.slow
@pytest.mark.soak
def test_soak_1m_multi_epoch_within_cache_budget():
    """Three epochs over a 1,000,000-validator registry: the run passes
    every deterministic gate, each per-epoch snapshot stays inside the
    SSZ byte budget (a slow leak would fail at the epoch it starts),
    and host peak memory stays bounded.

    Peak memory is pinned via ru_maxrss rather than tracemalloc:
    tracing roughly doubles this run's ~7-minute wall time for no
    extra signal — the registry's big allocations are numpy planes
    that RSS captures just as well (measured 10.5 GiB on this image).
    """
    import resource

    r = run_scenario("soak-1m")
    assert r["pass"], [s["name"] for s in r["slo"] if not s["ok"]]
    assert r["fingerprint"] == SOAK_1M_FINGERPRINT
    assert r.get("first_violation_epoch") is None

    budget = 268_435_456  # mirrors the registered max_ssz_cache_bytes
    epochs = r["epochs"]
    assert len(epochs) == 3
    for rec in epochs:
        assert 0 < rec["facts"]["ssz_cache_bytes"] <= budget, rec
        gates = {g["name"]: g["ok"] for g in rec["slo"]}
        assert gates.get("ssz_cache_bytes", True), rec

    peak_mib = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
    assert peak_mib < 14 * 1024, f"host peak {peak_mib:.0f} MiB"
