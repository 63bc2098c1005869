"""Static invariant analyzer suite.

Locks down six surfaces: (1) the live repo stays clean under the full
audit (zero unwaivered findings, and the waiver file is honoured) —
the fast AST tier runs in-module, the minutes-scale ``range`` kernel
proofs under ``slow``; (2) the seeded corpus under
``tests/fixtures/lint/`` makes every lint family fire on at least two
distinct violation shapes — including the two-lock deadlock cycle,
the four range-family theorem classes, and the six spmd finding
shapes; (3) the CLI exit codes (including ``--changed`` family
scoping) and the waiver/stale-waiver mechanics; (4) one chaos sync
soak runs under the runtime lockcheck sanitizer and the observed
acquisition order is verified against the static lock-order graph;
(5) the range family's live-tree proofs: strict/quasi output
contracts and the exact LFp bound algebra hold on the real kernels;
(6) the spmd family's live-tree proofs: the staged sharded programs
pass all four SPMD theorem classes at zero waivers, with warm replay
through the shared proof cache (runtime half in test_spmd_probe).
"""

import json
import os
import re
import subprocess
import sys
import threading

import pytest

from lighthouse_tpu.analysis import (
    ALL_FAMILIES,
    AST_FAMILIES,
    AuditConfig,
    load_config,
    range_lint,
    run_audit,
    spmd_lint,
)
from lighthouse_tpu.analysis.lock_lint import static_lock_order
from lighthouse_tpu.analysis.waivers import (
    Waiver,
    WaiverFormatError,
    load_waivers,
    parse_toml_subset,
)
from lighthouse_tpu.utils.lockcheck import (
    CheckedLock,
    LockOrderRecorder,
    instrument,
)

pytestmark = pytest.mark.analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = "tests/fixtures/lint"
LINT_TOML = os.path.join(REPO, FIXTURES, "lint.toml")
WAIVERS = os.path.join(REPO, "lighthouse_tpu", "analysis", "waivers.toml")


@pytest.fixture(scope="module")
def live_result():
    # AST tier only: the range family traces kernels for minutes and has
    # its own live proofs below (fast subset) and under slow (full)
    return run_audit(REPO, AuditConfig(families=AST_FAMILIES),
                     waivers=WAIVERS)


@pytest.fixture(scope="module")
def corpus_result():
    return run_audit(REPO, load_config(LINT_TOML))


def _by_rule(result):
    out = {}
    for v in result.violations:
        out.setdefault(v.rule, []).append(v)
    return out


# -- the live repo -------------------------------------------------------


def test_live_repo_is_clean(live_result):
    assert live_result.ok, "live repo audit found unwaivered findings:\n" + (
        "\n".join(str(v) for v in live_result.violations)
    )


def test_live_audit_is_fast(live_result):
    # acceptance bound: whole-repo audit completes in well under a minute
    assert live_result.elapsed_s < 60.0
    assert live_result.files_scanned > 100  # it actually scanned the repo


def test_live_lock_order_graph_derives_sync_edges(live_result):
    edges = {(e.src, e.dst) for e in live_result.lock_edges}
    assert ("SyncManager._tick_lock", "SyncManager._lock") in edges
    assert ("SyncManager._tick_lock", "SyncManager._chain_lock") in edges


# -- seeded corpus: every family fires on >=2 shapes ---------------------


def test_corpus_fails(corpus_result):
    assert not corpus_result.ok


def test_lock_discipline_fires_on_both_shapes(corpus_result):
    symbols = {v.symbol for v in _by_rule(corpus_result)["lock-discipline"]}
    assert "BareMutation._count" in symbols        # bare mutation
    assert "BareContainerRead._items" in symbols   # bare container read


def test_lock_order_fires_on_direct_and_call_resolved_cycles(corpus_result):
    vios = _by_rule(corpus_result)["lock-order"]
    classes = {v.symbol.split(".")[0] for v in vios}
    assert "NestedDeadlock" in classes   # nested `with` in opposite orders
    assert "CallDeadlock" in classes     # cycle through self.m() resolution
    assert all(" -> " in v.message for v in vios)


def test_never_raise_fires_on_both_shapes(corpus_result):
    symbols = {v.symbol for v in _by_rule(corpus_result)["never-raise"]}
    assert "Shaky.run" in symbols   # unprotected raising statement
    assert "Relay.send" in symbols  # covering try whose handler re-raises


def test_broad_except_fires_twice_and_exempts_reraise(corpus_result):
    vios = [
        v for v in _by_rule(corpus_result)["broad-except"]
        if v.path.endswith("broad_bad.py")
    ]
    msgs = " | ".join(v.message for v in vios)
    assert len(vios) == 2  # cleanup_then_propagate's re-raise is exempt
    assert "bare `except:`" in msgs
    assert "`except BaseException`" in msgs


def test_metrics_registry_fires_on_ref_orphan_and_doc(corpus_result):
    symbols = {v.symbol for v in _by_rule(corpus_result)["metrics-registry"]}
    assert "FIXTURE_GHOST" in symbols        # unknown reference
    assert "FIXTURE_ORPHAN" in symbols       # registered but never used
    assert "fixture_ghost_total" in symbols  # doc names unregistered metric


def test_fault_sites_fire_on_unknown_orphan_and_prefix(corpus_result):
    symbols = {v.symbol for v in _by_rule(corpus_result)["fault-sites"]}
    assert "fixture.bogus" in symbols    # fired but unregistered
    assert "fixture.orphan" in symbols   # registered but never fired
    assert "fixture.dyn.*" in symbols    # registered prefix never fired


def test_chaos_spec_fires_on_bad_kind_and_unknown_site(corpus_result):
    vios = _by_rule(corpus_result)["chaos-spec"]
    symbols = {v.symbol for v in vios}
    assert "fixture.good=frobnicate:1.0" in symbols  # unparsable kind
    assert "fixture.bogus" in symbols                # unregistered site
    # the `--chaos <site>=<kind>` usage template is skipped
    assert not any("<site>" in s for s in symbols)


def test_scenario_spec_fires_on_unknown_name_only(corpus_result):
    vios = _by_rule(corpus_result)["scenario-spec"]
    symbols = {v.symbol for v in vios}
    assert symbols == {"nonexistent-fixture"}  # the two valid names pass
    # the `--scenario <name>` usage template is skipped
    assert not any("<name>" in s for s in symbols)


def test_serve_port_fires_on_non_int_and_out_of_range(corpus_result):
    vios = _by_rule(corpus_result)["serve-port"]
    symbols = {v.symbol for v in vios}
    assert symbols == {"banana", "70000"}  # 5053 and 0 pass
    # the `--serve-port <port>` usage template is skipped
    assert not any("<port>" in s for s in symbols)


def test_partition_rules_fire_on_every_seeded_shape(corpus_result):
    vios = _by_rule(corpus_result)["partition-rules"]
    msgs: dict[str, str] = {}
    for v in vios:
        msgs[v.symbol] = msgs.get(v.symbol, "") + " | " + v.message
    assert "does not compile" in msgs["[invalid"]
    assert "unregistered spec token 'warp'" in msgs["^ghost$"]
    assert "matches no partition rule" in msgs["wbits"]       # orphan leaf
    shadows = [v for v in vios if v.symbol == "^pk/x$"]
    assert shadows and "shadowed" in shadows[0].message
    dead = [v for v in vios if v.symbol == "^ghost$"
            and "dead rule" in v.message]
    assert dead and "matches no operand leaf" in dead[0].message
    # the healthy first rule is not flagged
    assert not any(v.symbol == "^pk/" for v in vios)


def test_partition_rules_live_table_binds_runtime_leaves():
    """The audited constants are the ones the program actually uses:
    every OPERAND_LEAVES name resolves through PARTITION_RULES to a
    registered spec token via the live matcher."""
    from lighthouse_tpu.parallel import partition as P

    for leaf in P.OPERAND_LEAVES:
        token = next(
            (tok for rx, tok in P.PARTITION_RULES if re.search(rx, leaf)),
            None,
        )
        assert token in P.SPEC_TOKENS, leaf


def test_aot_manifest_fires_on_every_seeded_shape(corpus_result):
    vios = _by_rule(corpus_result)["aot-manifest"]
    symbols = {v.symbol for v in vios}
    # direction 1: registered program with no kernel definition (ghost)
    assert "fixture_kernel_ghost" in symbols
    assert "fixture_kernel_good" not in symbols
    # direction 2: manifest entry naming an unregistered kernel (orphan
    # / stale working set), a signature that does not verify, and an
    # entry missing the metadata prewarm keys on
    assert "bbbbbbbbbbbb" in symbols
    assert "signature" in symbols
    assert "cccccccccccc.cache_key" in symbols
    # the correctly-signed manifest over a registered kernel is clean
    assert not any(
        v.path.endswith("aot_manifest_good.json") for v in vios
    )


def test_aot_manifest_skipped_when_defs_absent():
    # corpora without the AOT store (older fixture corpora) run the
    # other families without an aot-manifest finding
    from lighthouse_tpu.analysis import registry_lint

    out = registry_lint.run(
        [("a.py", "x = 1\n")], [],
        metrics_defs_path="nope_metrics.py",
        faults_defs_path="nope_faults.py",
        aot_defs_path="nope_aot.py",
    )
    assert not [v for v in out if v.rule == "aot-manifest"]


def test_aot_manifest_live_registry_binds_backend_kernels():
    """The audited constants are the ones the store actually captures:
    every AOT_KERNELS name is a callable kernel in the live backend,
    and the AST parse sees exactly the runtime tuple."""
    from lighthouse_tpu.analysis.registry_lint import aot_manifest_defs
    from lighthouse_tpu.crypto.bls.jax_backend import aot
    from lighthouse_tpu.crypto.bls.jax_backend import backend as B

    path = "lighthouse_tpu/crypto/bls/jax_backend/aot.py"
    with open(os.path.join(REPO, path), encoding="utf-8") as f:
        kernels = aot_manifest_defs(f.read(), path)
    assert set(kernels) == set(aot.AOT_KERNELS)
    for name in kernels:
        assert callable(getattr(B, name))


def test_tune_plan_fires_on_every_seeded_shape(corpus_result):
    vios = _by_rule(corpus_result)["tune-plan"]
    symbols = {v.symbol for v in vios}
    # direction 1: an arm routing through a toggle fp never defines
    assert "fix_ghost" in symbols
    assert "fix_good" not in symbols
    assert "fix_unproven" not in symbols  # registering unproven is legal
    # direction 2: audited plan tables — tampered signature, missing
    # install-currency field, non-power-of-2 shape, unknown arm, arm
    # with no range proof, and an unregistered kernel
    assert "plan_signature" in symbols
    assert "plan.device_kind" in symbols
    assert "plan.shapes[12]" in symbols
    assert "plan.shapes[16]" in symbols
    assert "plan.shapes[32]" in symbols
    assert "plan.shapes[64]" in symbols
    # the correctly-signed plan selecting the proven arm is clean
    assert not any(
        v.path.endswith("aot_manifest_good.json") for v in vios
    )


def test_tune_plan_skipped_when_defs_absent():
    # corpora without the autotuner (older fixture corpora) run the
    # other families without a tune-plan finding
    from lighthouse_tpu.analysis import registry_lint

    out = registry_lint.run(
        [("a.py", "x = 1\n")], [],
        metrics_defs_path="nope_metrics.py",
        faults_defs_path="nope_faults.py",
        tune_defs_path="nope_tune.py",
    )
    assert not [v for v in out if v.rule == "tune-plan"]


def test_tune_plan_live_registry_binds_proven_arms():
    """The AST parse sees exactly the runtime ARM_TABLE, every toggle is
    a real fp.py setter, and every proof program stands in the shipped
    RANGE_REPORT.json at zero range-family waivers — the legality bar
    ``autotune.tune`` trials against."""
    from lighthouse_tpu.analysis.registry_lint import tune_plan_defs
    from lighthouse_tpu.crypto.bls.jax_backend import autotune
    from lighthouse_tpu.crypto.bls.jax_backend import fp as F

    path = "lighthouse_tpu/crypto/bls/jax_backend/autotune.py"
    with open(os.path.join(REPO, path), encoding="utf-8") as f:
        arms = tune_plan_defs(f.read(), path)
    assert set(arms) == {a.arm for a in autotune.ARMS}
    for arm_id, (spec, toggle, value, proof, _line) in arms.items():
        runtime = autotune.arm_by_id(arm_id)
        assert (spec, toggle, value, proof) == (
            runtime.spec, runtime.toggle, runtime.value, runtime.proof
        )
        assert callable(getattr(F, toggle))
    # every shipped arm is provably legal to tune
    assert {a.arm for a in autotune.proven_arms()} == set(arms)


def test_integrity_corpus_fires_on_every_seeded_shape(corpus_result):
    vios = _by_rule(corpus_result)["integrity-corpus"]
    symbols = {v.symbol for v in vios}
    msgs = [v.message for v in vios]
    # malformed rows: wrong arity and a non-string member (2 shapes)
    assert sum("string triple" in m for m in msgs) == 2
    # unknown kinds the generator cannot materialise (2 shapes)
    assert "fix-bogus" in symbols
    assert "fix-maybe" in symbols
    # duplicate entry ids (2 shapes)
    assert sum("duplicate canary entry id" in m for m in msgs) == 2
    # one-sided corpus: no well-formed invalid canary survives
    assert any("no 'invalid' canary" in m for m in msgs)
    # claimed-but-unregistered chaos kinds (2 shapes)
    assert "silent-ghost" in symbols
    assert "silent-phantom" in symbols
    # registered silent-* kinds the coverage contract dropped (2 shapes)
    assert "silent-unclaimed-a" in symbols
    assert "silent-unclaimed-b" in symbols
    # good shapes stay clean
    assert "silent-good" not in symbols
    assert len(vios) == 11


def test_integrity_corpus_skipped_when_defs_absent():
    # corpora without the integrity layer (older fixture corpora) run
    # the other families without an integrity-corpus finding
    from lighthouse_tpu.analysis import registry_lint

    out = registry_lint.run(
        [("a.py", "x = 1\n")], [],
        metrics_defs_path="nope_metrics.py",
        faults_defs_path="nope_faults.py",
        integrity_defs_path="nope_integrity.py",
    )
    assert not [v for v in out if v.rule == "integrity-corpus"]
    # a present-but-empty defs file reports both missing registries
    direct = registry_lint.integrity_violations(
        [("gone.py", "x = 1\n")], "gone.py", "nope_faults.py",
    )
    assert {v.symbol for v in direct} == {
        "CANARY_CORPUS", "REQUIRED_CHAOS_KINDS",
    }


def test_integrity_corpus_live_registry_binds_runtime():
    """The AST parse sees exactly the runtime canary corpus, every
    claimed chaos kind is armable, and the live registries produce zero
    findings — the contract the sdc scenarios lean on."""
    from lighthouse_tpu.analysis.registry_lint import (
        _fault_kind_defs,
        integrity_defs,
        integrity_violations,
    )
    from lighthouse_tpu.integrity import corpus as corpus_mod
    from lighthouse_tpu.utils import faults as faults_mod

    int_path = "lighthouse_tpu/integrity/corpus.py"
    faults_path = "lighthouse_tpu/utils/faults.py"
    srcs = {}
    for path in (int_path, faults_path):
        with open(os.path.join(REPO, path), encoding="utf-8") as f:
            srcs[path] = f.read()
    corpus_node, kinds_node = integrity_defs(srcs[int_path], int_path)
    parsed_ids = [
        e.elts[0].value for e in corpus_node.value.elts
    ]
    assert parsed_ids == [r[0] for r in corpus_mod.CANARY_CORPUS]
    assert [
        x.value for x in kinds_node.value.elts
    ] == list(corpus_mod.REQUIRED_CHAOS_KINDS)
    registered = _fault_kind_defs(srcs[faults_path], faults_path)
    assert set(registered) == set(faults_mod._KINDS)
    for kind in corpus_mod.REQUIRED_CHAOS_KINDS:
        assert kind in faults_mod._KINDS
    assert not integrity_violations(
        list(srcs.items()), int_path, faults_path,
    )


def test_live_serve_port_docs_are_valid(live_result):
    # every concrete --serve-port example in README/docs must be a real
    # TCP port, same doc-example contract as --chaos / --scenario
    assert not [
        v for v in live_result.violations if v.rule == "serve-port"
    ]


def test_span_registry_fires_on_ghost_and_orphan(corpus_result):
    symbols = {v.symbol for v in _by_rule(corpus_result)["span-registry"]}
    assert "fixture.span.ghost" in symbols   # opened but unregistered
    assert "fixture.span.orphan" in symbols  # registered but never opened
    assert "fixture.span.good" not in symbols


def test_span_registry_skipped_when_defs_absent():
    from lighthouse_tpu.analysis import registry_lint

    # a corpus that never includes the defs file runs the other families
    # without a span-registry finding (run() skips, matching scenarios)
    out = registry_lint.run(
        [("a.py", "x = 1\n")], [],
        metrics_defs_path="nope_metrics.py",
        faults_defs_path="nope_faults.py",
        spans_defs_path="nope_spans.py",
    )
    assert not [v for v in out if v.rule == "span-registry"]
    # a direct call still reports the missing registry explicitly
    direct = registry_lint.span_violations([("a.py", "x = 1\n")], "gone.py")
    assert [v for v in direct if v.rule == "span-registry"]


def test_span_registry_parses_live_tracer_registry():
    from lighthouse_tpu.analysis.registry_lint import span_defs

    path = "lighthouse_tpu/obs/tracer.py"
    with open(os.path.join(REPO, path), encoding="utf-8") as f:
        spans = span_defs(f.read(), path)
    assert "pipeline.marshal" in spans
    assert "jit.compile" in spans
    assert len(spans) >= 10


def test_doc_metric_regex_catches_unregistered_seconds(corpus_result):
    symbols = {v.symbol for v in _by_rule(corpus_result)["metrics-registry"]}
    assert "fixture_ghost_seconds" in symbols
    # the widened regex also covers *_percent gauge tokens
    assert "fixture_ghost_percent" in symbols


def test_scenario_defs_parses_both_assignment_shapes():
    from lighthouse_tpu.analysis.registry_lint import scenario_defs

    plain = 'SCENARIOS = {\n    "a": 1,\n    "b": 2,\n}\n'
    annotated = 'SCENARIOS: dict[str, int] = {\n    "c": 3,\n}\n'
    assert set(scenario_defs(plain, "x.py")) == {"a", "b"}
    assert set(scenario_defs(annotated, "x.py")) == {"c"}


def test_scenario_family_skipped_when_defs_absent():
    # fixture-style corpora without a scenario registry must not trip
    # the family (registry_lint.run skips it when the file is missing)
    from lighthouse_tpu.analysis import registry_lint

    docs = [("doc.md", "use `--scenario anything-goes` here")]
    vios = registry_lint.run(
        {}, docs, metrics_defs_path=None,
        faults_defs_path=None, scenarios_defs_path="missing/spec.py",
    )
    assert not [v for v in vios if v.rule == "scenario-spec"]


def test_live_scenario_registry_matches_docs(live_result):
    # the live audit wires scenario/spec.py in by default; a clean run
    # proves every --scenario example in README/docs names a real spec
    assert not [
        v for v in live_result.violations if v.rule == "scenario-spec"
    ]


def test_host_sync_lint_fires_only_on_registered_functions(corpus_result):
    vios = [
        v for v in _by_rule(corpus_result)["jaxpr-hygiene"]
        if v.path.endswith("hostsync_bad.py")
    ]
    assert {v.symbol for v in vios} == {"dispatch", "resolve"}
    assert len(vios) == 3  # block_until_ready + np.asarray + float()
    # helper's .item() stays unflagged: it is not in the hot-path registry


# -- range family: seeded corpus shapes ----------------------------------


def test_range_overflow_fires_on_both_shapes(corpus_result):
    vios = _by_rule(corpus_result)["range-overflow"]
    by_prog = {v.symbol.split(":")[0] for v in vios}
    assert "fixture_unsplit_mac" in by_prog   # unsplit MAC wraps uint32
    assert "fixture_raw_sub" in by_prog       # biasless sub wraps below 0
    # findings carry the computed interval and the eqn site
    assert all("interval [" in v.message for v in vios)
    assert any("range_overflow.py" in v.message for v in vios)


def test_range_contract_fires_on_both_shapes(corpus_result):
    vios = _by_rule(corpus_result)["range-contract"]
    msgs = {v.symbol: v.message for v in vios}
    assert "fixture_skipped_carry:out0" in msgs    # quasi cap exceeded
    assert "fixture_unmasked_reduce:out0" in msgs  # strict cap exceeded
    assert "`quasi`" in msgs["fixture_skipped_carry:out0"]
    assert "`strict`" in msgs["fixture_unmasked_reduce:out0"]


def test_range_lfp_fires_on_unsound_constants(corpus_result):
    symbols = {v.symbol for v in _by_rule(corpus_result)["range-lfp"]}
    # divisor 700 over-claims the mont output bound (exact R/P ~ 630.05)
    assert "unsound:mont-output-bound@prod=2000" in symbols
    # pin 1.5 undershoots the exact reduce worst case
    assert "unsound:reduce-pin" in symbols
    # MAX_BOUND 2500 pushes the dropped top carry past 2^15
    assert "unsound:compress1-top-carry" in symbols


def test_range_slack_fires_on_loose_constants(corpus_result):
    symbols = {v.symbol for v in _by_rule(corpus_result)["range-slack"]}
    assert "loose:mont-output-bound@prod=2000" in symbols  # 62% slack
    assert "loose:reduce-pin" in symbols                   # 80% slack


# -- range family: live-tree proofs (fast subset) -------------------------

# op-level programs only: the minutes-scale whole-kernel composition
# traces (miller/wsm/megachains) run under ``slow`` below
FAST_RANGE_PROGRAMS = (
    "pallas_mont_mul", "pallas_mont_sqr", "xla_mont_mul", "xla_fp_add",
    "xla_fp_sub_k2", "xla_fp_sub_k256", "pallas_ksub_k2",
    "pallas_ksub_k256",
    # the MXU 13-bit dot-product core: op-level converters + the full
    # kernel pair (seconds, not the minutes-scale megachain trace)
    "mxu_mont_mul", "mxu_mont_sqr", "mxu_to13", "mxu_to15",
    "mxu_dot_cols",
)


@pytest.fixture(scope="module")
def live_range_fast():
    return range_lint.generate(REPO, AuditConfig(),
                               only=FAST_RANGE_PROGRAMS)


def test_live_kernels_prove_no_uint32_overflow(live_range_fast):
    violations, report = live_range_fast
    assert not [v for v in violations if v.rule == "range-overflow"], (
        [str(v) for v in violations]
    )
    # the interpreter actually walked the kernels
    assert report["programs"]["pallas_mont_mul"]["eqns"] > 500


def test_live_mont_kernels_prove_strict_contract(live_range_fast):
    violations, report = live_range_fast
    assert not [v for v in violations if v.rule == "range-contract"]
    for name in ("pallas_mont_mul", "pallas_mont_sqr", "xla_mont_mul"):
        assert report["programs"][name]["contracts_ok"]
        # _mont_reduce's masked carry chain: every output limb < 2^15
        assert max(report["programs"][name]["out_caps"]) < (1 << 15)


def test_live_fp_sub_bias_domination_proved(live_range_fast):
    # the per-k subtraction programs prove bias-limb domination (no
    # underflow) for every admissible subtrahend at the _k_for threshold
    violations, report = live_range_fast
    assert not violations
    for name in ("xla_fp_sub_k2", "pallas_ksub_k2", "xla_fp_sub_k256"):
        assert name in report["programs"]


def test_live_lfp_algebra_is_sound_and_tight():
    violations, checks = range_lint.lfp_check(range_lint.live_claims())
    assert not violations, [str(v) for v in violations]
    assert all(c["sound"] for c in checks)
    # slack is reported for the tightness-bearing checks and stays small
    slacks = {c["check"]: c["slack"] for c in checks
              if c["slack"] is not None}
    assert slacks["mont-output-bound@prod=2000"] < range_lint.SLACK_MAX
    assert slacks["reduce-pin"] < range_lint.SLACK_MAX


def test_live_mxu_report_budgets(live_range_fast):
    _violations, report = live_range_fast
    mxu = report["mxu"]
    # the current 26x15-bit direct dot-product column exceeds both MXU
    # accumulator budgets — that is the whole point of ROADMAP item 1
    assert mxu["current_rep"]["f32_ok"] is False
    assert mxu["current_rep"]["i32_ok"] is False
    assert mxu["max_w_f32"] == 9    # 43 limbs of <= 9 bits for f32
    assert mxu["max_w_i32"] == 13   # 30 limbs of <= 13 bits for int32
    rows = {r["w"]: r for r in mxu["limb_split_table"]}
    assert rows[9]["f32_ok"] and not rows[10]["f32_ok"]
    assert rows[13]["i32_ok"] and not rows[14]["i32_ok"]


def test_live_mxu_selected_split_proved(live_range_fast):
    """The shipped 13-bit re-limbing: selected split within budget, and
    the MXU kernel programs prove int32 safety (max dot-product interval
    under 2^31) with the strict 15-bit exit contract."""
    _violations, report = live_range_fast
    sel = report["mxu"]["selected_split"]
    assert sel["w"] == 13 and sel["limbs"] == 31  # incl. the spill row
    assert sel["i32_ok"] is True and sel["col_log2"] < 31
    assert "mxu_mont_mul" in sel["kernels"]
    for name in ("mxu_mont_mul", "mxu_mont_sqr", "mxu_dot_cols"):
        prog = report["programs"][name]
        assert 0 < prog["max_dot_log2"] < 31, (name, prog["max_dot_log2"])
    for name in ("mxu_mont_mul", "mxu_mont_sqr"):
        assert report["programs"][name]["contracts_ok"]
        assert max(report["programs"][name]["out_caps"]) < (1 << 15)
    # the converters hold their entry contracts
    assert max(report["programs"]["mxu_to13"]["out_caps"]) <= 8193
    assert max(report["programs"]["mxu_to15"]["out_caps"]) < (1 << 15)


# -- range family: proof cache (the >=5x warm-audit win) -------------------


def test_range_proof_cache_warm_agrees_with_cold(tmp_path, monkeypatch):
    """Cold trace and warm replay must be indistinguishable: identical
    violations, byte-identical report (so the RANGE_REPORT drift check
    cannot tell them apart), with the warm run all cache hits."""
    monkeypatch.setattr(range_lint, "_CACHE_FILE",
                        str(tmp_path / "proofcache.json"))
    only = ("mxu_to13", "mxu_to15")
    v_cold, r_cold = range_lint.generate(REPO, AuditConfig(), only=only)
    assert dict(range_lint._CACHE_STATS) == {"hits": 0, "misses": 2}
    v_warm, r_warm = range_lint.generate(REPO, AuditConfig(), only=only)
    assert dict(range_lint._CACHE_STATS) == {"hits": 2, "misses": 0}
    assert [v.to_dict() for v in v_cold] == [v.to_dict() for v in v_warm]
    assert json.dumps(r_cold, sort_keys=True) == json.dumps(
        r_warm, sort_keys=True)


def test_range_proof_cache_opt_out_never_touches_disk(tmp_path,
                                                      monkeypatch):
    """range_cache=False (the --no-cache flag) neither reads nor writes
    the cache file and reports zero hits."""
    monkeypatch.setattr(range_lint, "_CACHE_FILE",
                        str(tmp_path / "proofcache.json"))
    range_lint.generate(REPO, AuditConfig(range_cache=False),
                        only=("mxu_to13",))
    assert not (tmp_path / "proofcache.json").exists()
    assert range_lint._CACHE_STATS["hits"] == 0


def test_range_proof_cache_invalidates_on_kernel_edit(tmp_path,
                                                      monkeypatch):
    """A fingerprint mismatch (any kernel/lint edit) must force fresh
    traces instead of replaying stale verdicts."""
    monkeypatch.setattr(range_lint, "_CACHE_FILE",
                        str(tmp_path / "proofcache.json"))
    range_lint.generate(REPO, AuditConfig(), only=("mxu_to13",))
    monkeypatch.setattr(range_lint, "_proof_fingerprint",
                        lambda root: "edited-tree")
    range_lint.generate(REPO, AuditConfig(), only=("mxu_to13",))
    assert dict(range_lint._CACHE_STATS) == {"hits": 0, "misses": 1}


# -- range family: full registry + report drift (slow) --------------------


@pytest.mark.slow
def test_live_full_range_registry_is_clean_and_report_current():
    # whole registry including the miller/wsm composition traces, plus
    # the checked-in RANGE_REPORT.json drift check
    violations = range_lint.run(REPO, AuditConfig())
    assert not violations, [str(v) for v in violations]


@pytest.mark.slow
def test_range_report_drift_fails_audit(tmp_path):
    cfg = AuditConfig(range_report="no/such/RANGE_REPORT.json")
    violations = range_lint.run(REPO, cfg, only=())
    # ...a missing report is itself a violation pointing at the fix
    missing = [v for v in violations if v.rule == "range-report"]
    assert missing and "--write-range-report" in missing[0].message


def test_range_report_drift_detector_unit(tmp_path, monkeypatch):
    # unit-level: corrupt a copy of the checked-in report and verify the
    # drift check names the changed path (no kernel tracing involved)
    import json

    src = os.path.join(REPO, "RANGE_REPORT.json")
    with open(src, encoding="utf-8") as f:
        report = json.load(f)
    report["mxu"]["max_w_f32"] = 99
    bad = tmp_path / "RANGE_REPORT.json"
    bad.write_text(json.dumps(report))

    monkeypatch.setattr(range_lint, "generate",
                        lambda root, cfg, only=(): ([], json.loads(
                            json.dumps(dict(report, mxu=dict(
                                report["mxu"], max_w_f32=9))))))
    cfg = AuditConfig(range_report=str(bad.relative_to(tmp_path)))
    violations = range_lint.run(str(tmp_path), cfg)
    drift = [v for v in violations if v.rule == "range-report"]
    assert drift and "drift" in drift[0].symbol


# -- spmd family: seeded corpus fires shape by shape ----------------------


def test_spmd_collective_fires_on_axis_and_divergence(corpus_result):
    syms = sorted(v.symbol for v in _by_rule(corpus_result)["spmd-collective"])
    assert syms == [
        "fixture_bad_axis_gather:all_gather@cols",
        "fixture_bad_axis_psum:psum@rows",
        "fixture_cond_gather_varying:all_gather:diverging",
        "fixture_cond_psum_varying:psum:diverging",
    ]


def test_spmd_replication_fires_on_leak_ring_and_cond(corpus_result):
    syms = sorted(
        v.symbol for v in _by_rule(corpus_result)["spmd-replication"]
    )
    assert syms == [
        "fixture_cond_gather_varying:out0",
        "fixture_cond_psum_varying:out0",
        "fixture_rep_axis_index_leak:out0",
        "fixture_rep_partial_ring:out0",
    ]


def test_spmd_bounds_fires_on_unmasked_and_wrong_bound(corpus_result):
    found = _by_rule(corpus_result)["spmd-bounds"]
    assert sorted(v.symbol.split("@")[0] for v in found) == [
        "fixture_gather_unmasked:gather",
        "fixture_gather_wrong_bound:gather",
    ]
    by_prog = {v.symbol.split(":")[0]: v.message for v in found}
    # the unmasked take sees the full gathered slot range...
    assert "[0, 11]" in by_prog["fixture_gather_unmasked"]
    # ...the off-by-two mask narrows it, but not enough
    assert "[0, 5]" in by_prog["fixture_gather_wrong_bound"]
    for msg in by_prog.values():
        assert "escapes the local shard bound [0, 3]" in msg


def test_spmd_pad_fires_on_combines_and_fills(corpus_result):
    found = _by_rule(corpus_result)["spmd-pad"]
    combines = sorted(
        v.symbol.split("@")[0] for v in found if "@" in v.symbol
    )
    assert combines == [
        "fixture_prod_combine:reduce_prod",
        "fixture_sum_combine:reduce_sum",
    ]
    cols = sorted(v.symbol for v in found if "@" not in v.symbol)
    assert cols == (
        [f"fixture_pad_mean_fill:col{j}" for j in (5, 6, 7)]
        + [f"fixture_pad_zero_fill:col{j}" for j in (5, 6, 7)]
    )


def test_spmd_donate_fires_on_ungated_and_read_after(corpus_result):
    found = _by_rule(corpus_result)["spmd-donate"]
    assert sorted(v.symbol for v in found) == [
        "read-after-donate", "read-after-donate",
        "ungated-donation", "ungated-donation",
    ]
    reads = sorted(
        v.message.split("'")[1] for v in found
        if v.symbol == "read-after-donate"
    )
    assert reads == ["a", "b"]  # both donated buffers are caught


def test_spmd_corpus_fires_every_program(corpus_result):
    progs = {
        v.symbol.split(":")[0]
        for v in corpus_result.violations
        if v.rule.startswith("spmd-") and v.symbol.startswith("fixture_")
    }
    assert len(progs) == 12  # every registered fixture program fired


# -- spmd family: live-tree proofs + shared proof cache --------------------


@pytest.fixture(scope="module")
def live_spmd():
    return run_audit(REPO, AuditConfig(families=("spmd",)), waivers=WAIVERS)


def test_live_spmd_prover_is_clean_at_zero_waivers(live_spmd):
    assert live_spmd.ok, "live spmd audit found findings:\n" + "\n".join(
        str(v) for v in live_spmd.violations
    )
    assert not [w for w in live_spmd.waived
                if w.rule.startswith("spmd-")], (
        "the spmd family is a zero-waiver surface"
    )


def test_live_spmd_registry_covers_the_staged_surfaces():
    names = {p.name for p in spmd_lint.build_live_programs()}
    # flat + registry verify programs at three width/batch shapes,
    # the pad stages, and the ring-reduce fold
    for w, b, n in ((2, 5, 8), (4, 10, 16), (8, 13, 40)):
        assert f"verify_flat_w{w}_b{b}" in names
        assert f"verify_registry_w{w}_b{b}_n{n}" in names
        assert f"pad_operands_w{w}_b{b}" in names
        assert f"pad_slots_w{w}_b{b}" in names
        assert f"ring_reduce_w{w}" in names
    # non-divisible remainder coverage: 13 over 8 and 10 over 4
    assert "verify_flat_w8_b13" in names
    # the other dispatch consumers' characteristic shapes
    assert "stream_chunk_w8_b64" in names
    assert "pod_canary_w4_b4" in names


def test_spmd_declared_axes_parse_from_mesh_source():
    axes = spmd_lint._declared_axes_live(REPO)
    assert "batch" in axes


def test_spmd_proof_cache_warm_agrees_and_preserves_range_keys(
        tmp_path, monkeypatch):
    """Both traced families share .range_proof_cache.json under their
    own fingerprints: a write from either side must preserve the
    other's sections, and the spmd warm replay must be verdict-
    identical to the cold trace."""
    monkeypatch.setattr(range_lint, "_CACHE_FILE",
                        str(tmp_path / "proofcache.json"))
    range_lint.generate(REPO, AuditConfig(), only=("mxu_to13",))
    v_cold = spmd_lint.generate(REPO, AuditConfig())
    cold = dict(spmd_lint._CACHE_STATS)
    assert cold["misses"] > 0 and cold["hits"] == 0
    v_warm = spmd_lint.generate(REPO, AuditConfig())
    assert dict(spmd_lint._CACHE_STATS) == {
        "hits": cold["misses"], "misses": 0,
    }
    assert [v.to_dict() for v in v_cold] == [v.to_dict() for v in v_warm]
    doc = json.loads((tmp_path / "proofcache.json").read_text())
    assert "fingerprint" in doc and "programs" in doc  # range intact
    assert "spmd_fingerprint" in doc and "spmd_programs" in doc
    # the range side still warm-replays through the shared file
    range_lint.generate(REPO, AuditConfig(), only=("mxu_to13",))
    assert dict(range_lint._CACHE_STATS) == {"hits": 1, "misses": 0}


def test_spmd_proof_cache_invalidates_on_prover_edit(tmp_path, monkeypatch):
    monkeypatch.setattr(range_lint, "_CACHE_FILE",
                        str(tmp_path / "proofcache.json"))
    spmd_lint.generate(REPO, AuditConfig())
    first = dict(spmd_lint._CACHE_STATS)
    monkeypatch.setattr(spmd_lint, "_spmd_fingerprint",
                        lambda root: "edited-prover")
    spmd_lint.generate(REPO, AuditConfig())
    assert spmd_lint._CACHE_STATS["hits"] == 0
    assert spmd_lint._CACHE_STATS["misses"] == first["misses"]


def test_spmd_cache_opt_out_never_touches_disk(tmp_path, monkeypatch):
    monkeypatch.setattr(range_lint, "_CACHE_FILE",
                        str(tmp_path / "proofcache.json"))
    spmd_lint.generate(REPO, AuditConfig(range_cache=False))
    assert not (tmp_path / "proofcache.json").exists()
    assert spmd_lint._CACHE_STATS["hits"] == 0


def test_range_fingerprint_covers_the_sharded_program_sources():
    deps = range_lint._fingerprint_deps(REPO)
    assert "lighthouse_tpu/parallel/partition.py" in deps
    assert "lighthouse_tpu/parallel/mesh.py" in deps
    assert any(d.endswith("jax_backend/fp.py") for d in deps)


def test_spmd_fingerprint_tracks_prover_and_kernels(monkeypatch):
    base = spmd_lint._spmd_fingerprint(REPO)
    # an edit to anything under the range fingerprint (kernels, the
    # partition/mesh sources) shifts the spmd fingerprint too
    monkeypatch.setattr(range_lint, "_proof_fingerprint",
                        lambda root: "kernel-edited")
    assert spmd_lint._spmd_fingerprint(REPO) != base


# -- CLI entrypoint ------------------------------------------------------


def _run_cli(*extra, timeout=120):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "static_audit.py"),
         "--quiet", "--no-history", *extra],
        cwd=REPO, capture_output=True, text=True, timeout=timeout,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )


def test_cli_exits_zero_on_live_repo():
    # fast AST tier; the full run including range is the slow test below
    proc = _run_cli("--only", ",".join(AST_FAMILIES))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS" in proc.stderr


@pytest.mark.slow
def test_cli_full_audit_exits_zero_with_range():
    proc = _run_cli(timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS" in proc.stderr


def test_cli_exits_nonzero_on_seeded_corpus():
    proc = _run_cli("--config", LINT_TOML)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "FAIL" in proc.stderr


def test_cli_list_families_and_only_validation():
    proc = _run_cli("--list-families")
    assert proc.returncode == 0
    assert proc.stdout.split() == ["lock", "raise", "registry", "jaxpr",
                                   "range", "spmd"]
    assert tuple(proc.stdout.split()) == ALL_FAMILIES
    proc = _run_cli("--only", "nonsense")
    assert proc.returncode == 2
    assert "unknown families" in proc.stderr


def test_cli_changed_excludes_only():
    proc = _run_cli("--changed", "--only", "spmd")
    assert proc.returncode == 2
    assert "mutually exclusive" in proc.stderr


def _load_cli_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "static_audit_cli", os.path.join(REPO, "tools", "static_audit.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_changed_scoping_maps_paths_to_families():
    sa = _load_cli_module()
    # docs-only diff: nothing to audit
    assert sa.families_for_paths([]) == ()
    assert sa.families_for_paths(["README.md", "STATUS.md"]) == ()
    # any python change gets the fast AST tier
    assert sa.families_for_paths(["lighthouse_tpu/obs/metrics.py"]) == \
        AST_FAMILIES
    # kernel sources pull in both traced families (the spmd programs
    # close over the kernels)
    assert sa.families_for_paths(
        ["lighthouse_tpu/crypto/bls/jax_backend/fp.py"]
    ) == ALL_FAMILIES
    # sharded-program sources pull in spmd but not range
    fams = sa.families_for_paths(["lighthouse_tpu/parallel/partition.py"])
    assert "spmd" in fams and "range" not in fams
    # analyzer/tooling edits escalate to everything
    assert sa.families_for_paths(
        ["lighthouse_tpu/analysis/spmd_lint.py"]) == ALL_FAMILIES
    assert sa.families_for_paths(["tools/bench.py"]) == ALL_FAMILIES


def test_changed_paths_reads_this_repo():
    sa = _load_cli_module()
    paths = sa._changed_paths(REPO)
    assert paths is None or isinstance(paths, list)


# -- waivers + TOML subset ----------------------------------------------


def test_parse_toml_subset_roundtrip():
    doc = parse_toml_subset(
        "\n".join([
            "# comment",
            "[audit]",
            'scan_roots = ["a", "b"]',
            "budget = 6",
            "strict = true",
            "[[waiver]]",
            'rule = "lock-*"',
            'path = "x/y.py"',
            'reason = "because"',
            "[[waiver]]",
            'rule = "never-raise"',
            'path = "z.py"',
            'reason = "also"',
        ])
    )
    assert doc["audit"] == {
        "scan_roots": ["a", "b"], "budget": 6, "strict": True,
    }
    assert [w["rule"] for w in doc["waiver"]] == ["lock-*", "never-raise"]


def test_parse_toml_subset_rejects_unsupported_value():
    with pytest.raises(WaiverFormatError):
        parse_toml_subset("[audit]\nx = 1.5\n")


def test_load_waivers_rejects_missing_reason(tmp_path):
    p = tmp_path / "w.toml"
    p.write_text('[[waiver]]\nrule = "lock-order"\npath = "a.py"\n')
    with pytest.raises(WaiverFormatError):
        load_waivers(str(p))


def test_waiver_moves_finding_to_waived():
    cfg = load_config(LINT_TOML)
    w = Waiver(rule="broad-except", path=f"{FIXTURES}/broad_bad.py",
               reason="seeded fixture")
    res = run_audit(REPO, cfg, [w])
    assert "broad-except" not in {v.rule for v in res.violations}
    assert sum(1 for v, _ in res.waived if v.rule == "broad-except") == 2


def test_stale_waiver_is_itself_a_violation():
    cfg = load_config(LINT_TOML)
    w = Waiver(rule="lock-order", path="no/such/file.py", reason="stale")
    res = run_audit(REPO, cfg, [w])
    stale = [v for v in res.violations if v.rule == "stale-waiver"]
    assert len(stale) == 1


def test_checked_in_waiver_file_parses():
    # the real waiver file must always load (a format error would make
    # the audit un-runnable exactly when someone adds a waiver)
    load_waivers(WAIVERS)


# -- runtime lockcheck sanitizer -----------------------------------------


class _TwoLocks:
    def __init__(self):
        self.a = threading.Lock()
        self.b = threading.Lock()


def test_checkedlock_records_nesting_edges():
    rec = LockOrderRecorder()
    obj = _TwoLocks()
    instrument(obj, {"a": "T.a", "b": "T.b"}, rec, force=True)
    with obj.a:
        with obj.b:
            pass
    assert rec.edges() == {("T.a", "T.b")}
    rec.verify({("T.a", "T.b")})  # subset + acyclic: passes


def test_verify_rejects_edge_missing_from_static_graph():
    rec = LockOrderRecorder()
    obj = _TwoLocks()
    instrument(obj, {"a": "T.a", "b": "T.b"}, rec, force=True)
    with obj.a:
        with obj.b:
            pass
    with pytest.raises(AssertionError, match="not in the static"):
        rec.verify(set())


def test_verify_rejects_observed_cycle():
    rec = LockOrderRecorder()
    obj = _TwoLocks()
    instrument(obj, {"a": "T.a", "b": "T.b"}, rec, force=True)
    with obj.a:
        with obj.b:
            pass
    with obj.b:
        with obj.a:
            pass
    with pytest.raises(AssertionError, match="cycle"):
        rec.verify({("T.a", "T.b"), ("T.b", "T.a")})


def test_reentrant_reacquire_adds_no_self_edge():
    rec = LockOrderRecorder()

    class R:
        def __init__(self):
            self.r = threading.RLock()

    obj = R()
    instrument(obj, {"r": "T.r"}, rec, force=True)
    with obj.r:
        with obj.r:
            pass
    assert rec.edges() == set()
    assert rec.acquisitions == 1  # the re-entry is not a new acquisition


def test_instrument_is_noop_without_flag(monkeypatch):
    monkeypatch.delenv("LIGHTHOUSE_TPU_LOCKCHECK", raising=False)
    obj = _TwoLocks()
    assert instrument(obj, {"a": "T.a"}, None) is None
    assert not isinstance(obj.a, CheckedLock)


def test_chaos_sync_soak_under_lockcheck():
    """Run a small chaos sync soak with the SyncManager's three locks
    wrapped, then assert every acquisition order observed at runtime is
    an edge the static analyzer derived from sync.py (and acyclic)."""
    from lighthouse_tpu.beacon import BeaconChainHarness
    from lighthouse_tpu.beacon.sync import (
        SyncManager,
        SyncPeer,
        SyncState,
        serve_blocks_by_range,
    )
    from lighthouse_tpu.network import rpc
    from lighthouse_tpu.network.peer_manager import PeerManager

    ahead = BeaconChainHarness(n_validators=16)
    ahead.extend_chain(8)
    fresh = BeaconChainHarness(n_validators=16)
    pm = PeerManager()
    mgr = SyncManager(fresh.chain, peer_manager=pm, batch_slots=4,
                      request_timeout=0.3)

    serve = serve_blocks_by_range(ahead.chain, "altair")

    def request_blocks(start_slot, count):
        return [rpc.decode_response_chunk(c) for c in serve(start_slot, count)]

    calls = {"n": 0}

    def flaky(start_slot, count):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("connection reset by peer")
        return request_blocks(start_slot, count)

    mgr.add_peer(SyncPeer(peer_id="flaky", head_slot=8,
                          request_blocks=flaky))
    mgr.add_peer(SyncPeer(peer_id="good", head_slot=8,
                          request_blocks=request_blocks))

    rec = LockOrderRecorder()
    instrument(mgr, {"_tick_lock": "SyncManager._tick_lock",
                     "_lock": "SyncManager._lock",
                     "_chain_lock": "SyncManager._chain_lock"},
               rec, force=True)

    assert mgr.tick() == SyncState.SYNCED
    assert fresh.chain.head_root == ahead.chain.head_root
    assert rec.acquisitions > 0

    rel = "lighthouse_tpu/beacon/sync.py"
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        static = static_lock_order([(rel, f.read())])
    assert ("SyncManager._tick_lock", "SyncManager._lock") in static
    rec.verify(static)


# -- scenario-fixture corpus (the committed regression scenarios) --------


def test_scenario_fixture_fires_on_every_seeded_shape(corpus_result):
    vios = _by_rule(corpus_result)["scenario-fixture"]
    symbols = {v.symbol for v in vios}
    assert "broken" in symbols            # non-JSON fixture
    assert "other-name" in symbols        # name != file stem
    assert "seed" in symbols              # required field missing
    assert "max_unregistered" in symbols  # SLO key not in DEFAULT_SLO
    assert "frobnicate" in symbols        # field not in _SPEC_JSON_FIELDS
    # the well-formed seeded fixture passes every check
    assert not any("regress-fixture-good" in v.path for v in vios)


def test_live_scenario_fixture_corpus_replays(live_result):
    # every committed regression fixture under tests/fixtures/scenarios
    # parses, matches its stem, names only registered SLO keys, and
    # round-trips through the real parse_scenario_arg — zero waivers
    assert not [
        v for v in live_result.violations if v.rule == "scenario-fixture"
    ]


def test_scenario_fixture_schema_parses_spec_module():
    from lighthouse_tpu.analysis.registry_lint import (
        scenario_fixture_schema,
    )
    from lighthouse_tpu.scenario.spec import _SPEC_JSON_FIELDS, DEFAULT_SLO

    path = os.path.join(REPO, "lighthouse_tpu", "scenario", "spec.py")
    with open(path) as f:
        fields, slo_keys = scenario_fixture_schema(f.read(), path)
    # the AST view must bind the live literals exactly — a drifted
    # schema would silently stop validating the corpus
    assert fields == set(_SPEC_JSON_FIELDS)
    assert slo_keys == set(DEFAULT_SLO)
