"""Repo-wide static invariant analyzer.

One entrypoint (``JAX_PLATFORMS=cpu python tools/static_audit.py``) runs six lint
families over the package and emits a JSON report, failing on any
unwaivered violation:

* ``lock_lint``     — lock-discipline race detector + lock-order graph
* ``raise_lint``    — never-raise proofs + broad-except ban
* ``registry_lint`` — metrics / fault-site / chaos-spec / trace-span
  consistency
* ``jaxpr_lint``    — dispatch hot-path host-sync ban (the jaxpr walk
  and zero-dim guard live here too, but tracing is driven by
  ``tools/dispatch_audit.py`` and the test suite, not by the audit)
* ``range_lint``    — limb-range abstract interpreter: uint32
  overflow/carry proofs for every registered field kernel, LFp bound
  algebra soundness, and the MXU-readiness report
  (``RANGE_REPORT.json``)
* ``spmd_lint``     — SPMD soundness prover: re-stages the sharded
  programs over an abstract mesh and proves collective legality,
  verdict replication, pad absorption, registry-gather bounds, and
  donation discipline

The pure-AST families finish in seconds; ``range`` and ``spmd`` trace
programs through jax and dominate the wall time (both replay cached
verdicts from ``.range_proof_cache.json`` on an untouched tree) — use
``tools/static_audit.py --only lock,raise,registry,jaxpr`` (see
``AST_FAMILIES``) for the fast tier.

Justified exceptions go in ``analysis/waivers.toml`` (see ``waivers``).
Everything is configurable so the seeded-violation fixture corpus under
``tests/fixtures/lint/`` can run the identical pipeline against its own
tiny registries.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field

from . import (
    jaxpr_lint,
    lock_lint,
    raise_lint,
    range_lint,
    registry_lint,
    spmd_lint,
)
from .report import Violation
from .waivers import Waiver, apply_waivers, load_waivers, parse_toml_subset

__all__ = [
    "AuditConfig", "AuditResult", "Violation", "Waiver",
    "run_audit", "load_config", "discover_files", "load_waivers",
    "jaxpr_lint", "lock_lint", "raise_lint", "range_lint",
    "registry_lint", "spmd_lint", "ALL_FAMILIES", "AST_FAMILIES",
]

DEFAULT_NEVER_RAISE = (
    "lighthouse_tpu/beacon/processor.py::ResilientVerifier.verify_batch",
    "lighthouse_tpu/beacon/sync.py::SyncManager.tick",
    "lighthouse_tpu/utils/faults.py::FaultInjector.maybe_fire",
    "lighthouse_tpu/beacon/processor.py::BeaconProcessor.try_send",
    "lighthouse_tpu/ingest/engine.py::IngestEngine.marshal_sets",
    "lighthouse_tpu/parallel/pod.py::PodVerifier.verify_batch",
    "lighthouse_tpu/serve/service.py::VerifyService.tick",
    "lighthouse_tpu/integrity/guard.py::IntegrityGuard.verify_batch",
)

ALL_FAMILIES = ("lock", "raise", "registry", "jaxpr", "range", "spmd")
# the pure-AST tier: no jax import, finishes in seconds
AST_FAMILIES = ("lock", "raise", "registry", "jaxpr")


@dataclass
class AuditConfig:
    # roots (files or directories, relative to the audit root) that form
    # the python corpus
    scan_roots: tuple = ("lighthouse_tpu", "tools", "tests", "bench.py")
    # path prefixes eligible for the lock-discipline family (test classes
    # carry no threading discipline; scanning them is pure noise)
    lock_scan_include: tuple = ("lighthouse_tpu/",)
    # never-raise proofs also only bind inside the package
    never_raise: tuple = DEFAULT_NEVER_RAISE
    safe_calls: tuple = ("BatchOutcome", "MarshalledBatch")
    metrics_defs: str = "lighthouse_tpu/utils/metrics.py"
    faults_defs: str = "lighthouse_tpu/utils/faults.py"
    scenarios_defs: str = "lighthouse_tpu/scenario/spec.py"
    # committed regression corpus the continuous scenario search feeds:
    # every *.json under this directory must replay (scenario-fixture
    # family); "" disables the family
    scenario_fixture_dir: str = "tests/fixtures/scenarios"
    spans_defs: str = "lighthouse_tpu/obs/tracer.py"
    # scenario-search mutation surface: the literal constants in
    # search_defs must reference registered shapes/tracks/knobs
    search_defs: str = "lighthouse_tpu/scenario/search.py"
    traffic_defs: str = "lighthouse_tpu/scenario/traffic.py"
    adversity_defs: str = "lighthouse_tpu/scenario/adversity.py"
    # sharded-program rule table: PARTITION_RULES must stay total over
    # OPERAND_LEAVES and free of dead/shadowed rules
    partition_defs: str = "lighthouse_tpu/parallel/partition.py"
    # AOT executable store: AOT_KERNELS (the registered program set)
    # must name kernels defined in backend.py, and any audited store
    # manifests must cross-reference it (orphan/stale entries + broken
    # signatures are findings)
    aot_defs: str = "lighthouse_tpu/crypto/bls/jax_backend/aot.py"
    aot_backend_defs: str = "lighthouse_tpu/crypto/bls/jax_backend/backend.py"
    aot_manifests: tuple = ()
    # kernel autotuner: ARM_TABLE arms must route through toggles defined
    # in fp.py, and audited manifest plan tables must verify (signature,
    # known proven arms, power-of-2 shapes, registered kernels)
    tune_defs: str = "lighthouse_tpu/crypto/bls/jax_backend/autotune.py"
    fp_defs: str = "lighthouse_tpu/crypto/bls/jax_backend/fp.py"
    # verdict-integrity layer: CANARY_CORPUS rows must be well-formed
    # with a valid/invalid mix, and REQUIRED_CHAOS_KINDS must
    # cross-reference the chaos kind registry both directions
    integrity_defs: str = "lighthouse_tpu/integrity/corpus.py"
    docs: tuple = ("README.md", "STATUS.md")
    hot_path: dict = field(
        default_factory=lambda: dict(jaxpr_lint.DEFAULT_HOT_PATH)
    )
    site_scan_exclude: tuple = ("tests/",)
    # prefixes dropped from the corpus entirely — the seeded-violation
    # fixture corpus must not fail the live audit
    exclude: tuple = ("tests/fixtures/lint/",)
    families: tuple = ALL_FAMILIES
    # range family: fixture registry override (python file exposing
    # build_programs()/LFP_CLAIMS; empty = the live kernel registry) and
    # the checked-in report the audit verifies against ("" skips the
    # drift check)
    range_defs: str = ""
    range_report: str = "RANGE_REPORT.json"
    # program names to restrict the range family to (empty = all)
    range_only: tuple = ()
    # replay per-program range verdicts from .range_proof_cache.json
    # when the kernel sources are unchanged (False / CLI --no-cache
    # forces fresh interpret-mode traces); the spmd family shares the
    # flag and the cache file under its own fingerprint
    range_cache: bool = True
    # spmd family: fixture registry override (python file exposing
    # build_programs()/DECLARED_AXES; empty = the live staged-program
    # registry traced out of parallel/partition.py + mesh.py)
    spmd_defs: str = ""


@dataclass
class AuditResult:
    root: str
    files_scanned: int
    violations: list        # unwaivered [Violation]
    waived: list            # [(Violation, reason)]
    lock_edges: list        # [lock_lint.LockEdge]
    elapsed_s: float
    family_seconds: dict = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.violations

    def summary(self) -> dict:
        counts: dict[str, int] = {}
        for v in self.violations:
            counts[v.rule] = counts.get(v.rule, 0) + 1
        return counts

    def to_dict(self) -> dict:
        return {
            "root": self.root,
            "pass": self.ok,
            "files_scanned": self.files_scanned,
            "elapsed_s": round(self.elapsed_s, 3),
            "family_seconds": {
                k: round(v, 3) for k, v in self.family_seconds.items()
            },
            "summary": self.summary(),
            "violations": [v.to_dict() for v in self.violations],
            "waived": [
                dict(v.to_dict(), reason=reason) for v, reason in self.waived
            ],
            "lock_order_edges": sorted(
                {(e.src, e.dst) for e in self.lock_edges}
            ),
        }


def discover_files(root: str, scan_roots) -> list[str]:
    """Repo-relative posix paths of every .py file under the roots."""
    out = []
    for entry in scan_roots:
        full = os.path.join(root, entry)
        if os.path.isfile(full) and entry.endswith(".py"):
            out.append(entry.replace(os.sep, "/"))
        elif os.path.isdir(full):
            for dirpath, dirnames, filenames in os.walk(full):
                dirnames[:] = sorted(
                    d for d in dirnames
                    if d not in ("__pycache__", ".git")
                )
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        rel = os.path.relpath(
                            os.path.join(dirpath, fn), root
                        )
                        out.append(rel.replace(os.sep, "/"))
    return sorted(set(out))


def _read_corpus(root, rel_paths):
    """[(rel_path, source)]; unreadable/unparsable files become
    parse-error violations rather than crashing the audit."""
    files, problems = [], []
    for rel in rel_paths:
        full = os.path.join(root, rel)
        try:
            with open(full, encoding="utf-8") as f:
                src = f.read()
            compile(src, rel, "exec", flags=0x400, dont_inherit=True)
        except SyntaxError as exc:
            problems.append(Violation(
                rule="parse-error", path=rel, line=exc.lineno or 0,
                symbol=rel, message=f"file does not parse: {exc.msg}",
            ))
            continue
        except OSError as exc:
            problems.append(Violation(
                rule="parse-error", path=rel, line=0,
                symbol=rel, message=f"unreadable: {exc}",
            ))
            continue
        files.append((rel, src))
    return files, problems


def load_config(path: str) -> AuditConfig:
    """Load an [audit] table (same TOML subset as waivers) into an
    AuditConfig — used by the fixture corpus to re-point the registries."""
    with open(path, encoding="utf-8") as f:
        doc = parse_toml_subset(f.read(), path)
    a = doc.get("audit", {})
    cfg = AuditConfig()
    if "scan_roots" in a:
        cfg.scan_roots = tuple(a["scan_roots"])
    if "lock_scan_include" in a:
        cfg.lock_scan_include = tuple(a["lock_scan_include"])
    if "never_raise" in a:
        cfg.never_raise = tuple(a["never_raise"])
    if "safe_calls" in a:
        cfg.safe_calls = tuple(a["safe_calls"])
    if "metrics_defs" in a:
        cfg.metrics_defs = a["metrics_defs"]
    if "faults_defs" in a:
        cfg.faults_defs = a["faults_defs"]
    if "scenarios_defs" in a:
        cfg.scenarios_defs = a["scenarios_defs"]
    if "scenario_fixture_dir" in a:
        cfg.scenario_fixture_dir = a["scenario_fixture_dir"]
    if "spans_defs" in a:
        cfg.spans_defs = a["spans_defs"]
    if "search_defs" in a:
        cfg.search_defs = a["search_defs"]
    if "traffic_defs" in a:
        cfg.traffic_defs = a["traffic_defs"]
    if "adversity_defs" in a:
        cfg.adversity_defs = a["adversity_defs"]
    if "partition_defs" in a:
        cfg.partition_defs = a["partition_defs"]
    if "aot_defs" in a:
        cfg.aot_defs = a["aot_defs"]
    if "aot_backend_defs" in a:
        cfg.aot_backend_defs = a["aot_backend_defs"]
    if "aot_manifests" in a:
        cfg.aot_manifests = tuple(a["aot_manifests"])
    if "tune_defs" in a:
        cfg.tune_defs = a["tune_defs"]
    if "fp_defs" in a:
        cfg.fp_defs = a["fp_defs"]
    if "integrity_defs" in a:
        cfg.integrity_defs = a["integrity_defs"]
    if "docs" in a:
        cfg.docs = tuple(a["docs"])
    if "site_scan_exclude" in a:
        cfg.site_scan_exclude = tuple(a["site_scan_exclude"])
    if "exclude" in a:
        cfg.exclude = tuple(a["exclude"])
    if "families" in a:
        cfg.families = tuple(a["families"])
    if "range_defs" in a:
        cfg.range_defs = a["range_defs"]
    if "range_report" in a:
        cfg.range_report = a["range_report"]
    if "range_only" in a:
        cfg.range_only = tuple(a["range_only"])
    if "range_cache" in a:
        cfg.range_cache = bool(a["range_cache"])
    if "spmd_defs" in a:
        cfg.spmd_defs = a["spmd_defs"]
    if "hot_path" in a:
        # entries are "relpath::fn" strings
        hp: dict[str, list] = {}
        for entry in a["hot_path"]:
            p, _, fn = entry.partition("::")
            hp.setdefault(p, []).append(fn)
        cfg.hot_path = {p: tuple(fns) for p, fns in hp.items()}
    return cfg


def run_audit(
    root: str,
    config: AuditConfig | None = None,
    waivers: list[Waiver] | str | None = None,
) -> AuditResult:
    t0 = time.perf_counter()
    cfg = config or AuditConfig()
    if isinstance(waivers, str):
        waivers = load_waivers(waivers)
    waivers = list(waivers or ())

    rel_paths = discover_files(root, cfg.scan_roots)
    if cfg.exclude:
        rel_paths = [
            p for p in rel_paths if not p.startswith(tuple(cfg.exclude))
        ]
    files, violations = _read_corpus(root, rel_paths)

    fam_t: dict[str, float] = {}

    lock_edges: list = []
    if "lock" in cfg.families:
        t = time.perf_counter()
        lock_files = [
            (p, s) for p, s in files
            if p.startswith(tuple(cfg.lock_scan_include))
        ]
        lock_violations, lock_edges = lock_lint.run(lock_files)
        violations.extend(lock_violations)
        fam_t["lock"] = time.perf_counter() - t

    if "raise" in cfg.families:
        t = time.perf_counter()
        for p, s in files:
            violations.extend(raise_lint.broad_except_violations(p, s))
        package_files = [
            (p, s) for p, s in files
            if p.startswith(tuple(cfg.lock_scan_include))
        ]
        violations.extend(raise_lint.never_raise_violations(
            package_files, cfg.never_raise, cfg.safe_calls
        ))
        fam_t["raise"] = time.perf_counter() - t

    if "registry" in cfg.families:
        t = time.perf_counter()
        docs = []
        for rel in cfg.docs:
            full = os.path.join(root, rel)
            try:
                with open(full, encoding="utf-8") as f:
                    docs.append((rel, f.read()))
            except OSError:
                violations.append(Violation(
                    rule="parse-error", path=rel, line=0, symbol=rel,
                    message="doc listed in audit config is unreadable",
                ))
        # the parse_scenario_arg round-trip only binds against the live
        # registry (fixture corpora re-point scenarios_defs at fakes)
        live_scenarios = (
            cfg.scenarios_defs == AuditConfig.scenarios_defs
        )
        # store manifests are JSON, outside the python corpus: read them
        # the way docs are read, unreadable ones become findings
        manifests = []
        for rel in cfg.aot_manifests:
            full = os.path.join(root, rel)
            try:
                with open(full, encoding="utf-8") as f:
                    manifests.append((rel, f.read()))
            except OSError:
                violations.append(Violation(
                    rule="parse-error", path=rel, line=0, symbol=rel,
                    message="AOT manifest listed in audit config is "
                            "unreadable",
                ))
        # committed scenario fixtures are JSON, outside the python
        # corpus: glob the corpus directory the way manifests are read
        scenario_fixtures = []
        if cfg.scenario_fixture_dir:
            fix_dir = os.path.join(root, cfg.scenario_fixture_dir)
            if os.path.isdir(fix_dir):
                for fn in sorted(os.listdir(fix_dir)):
                    if not fn.endswith(".json"):
                        continue
                    rel = f"{cfg.scenario_fixture_dir}/{fn}"
                    try:
                        with open(os.path.join(fix_dir, fn),
                                  encoding="utf-8") as f:
                            scenario_fixtures.append((rel, f.read()))
                    except OSError:
                        violations.append(Violation(
                            rule="parse-error", path=rel, line=0,
                            symbol=rel,
                            message="scenario fixture is unreadable",
                        ))
        violations.extend(registry_lint.run(
            files, docs, cfg.metrics_defs, cfg.faults_defs,
            cfg.site_scan_exclude,
            scenarios_defs_path=cfg.scenarios_defs,
            spans_defs_path=cfg.spans_defs,
            scenario_arg_validator=(
                registry_lint.default_scenario_arg_validator
                if live_scenarios else None
            ),
            search_defs_path=cfg.search_defs,
            traffic_defs_path=cfg.traffic_defs,
            adversity_defs_path=cfg.adversity_defs,
            partition_defs_path=cfg.partition_defs,
            aot_defs_path=cfg.aot_defs,
            aot_backend_defs_path=cfg.aot_backend_defs,
            aot_manifests=manifests,
            tune_defs_path=cfg.tune_defs,
            fp_defs_path=cfg.fp_defs,
            scenario_fixtures=scenario_fixtures,
            integrity_defs_path=cfg.integrity_defs,
        ))
        fam_t["registry"] = time.perf_counter() - t

    if "jaxpr" in cfg.families:
        t = time.perf_counter()
        violations.extend(jaxpr_lint.run(files, cfg.hot_path))
        fam_t["jaxpr"] = time.perf_counter() - t

    if "range" in cfg.families:
        t = time.perf_counter()
        violations.extend(range_lint.run(root, cfg, only=cfg.range_only))
        fam_t["range"] = time.perf_counter() - t

    if "spmd" in cfg.families:
        t = time.perf_counter()
        violations.extend(spmd_lint.run(root, cfg, files))
        fam_t["spmd"] = time.perf_counter() - t

    violations.sort(key=lambda v: (v.path, v.line, v.rule, v.symbol))
    failing, waived = apply_waivers(violations, waivers)
    for w in waivers:
        if w.used == 0:
            failing.append(Violation(
                rule="stale-waiver", path="analysis/waivers.toml", line=0,
                symbol=f"{w.rule}:{w.path}:{w.symbol}",
                message=(
                    "waiver matches nothing — the violation it excused is "
                    "gone; delete the waiver"
                ),
            ))
    return AuditResult(
        root=root,
        files_scanned=len(files),
        violations=failing,
        waived=waived,
        lock_edges=lock_edges,
        elapsed_s=time.perf_counter() - t0,
        family_seconds=fam_t,
    )
