#!/usr/bin/env python3
"""Repo-wide static invariant audit (lighthouse_tpu.analysis front-end).

Runs the six lint families — lock-discipline + lock-order graph,
never-raise/broad-except, registry consistency (metrics / fault sites /
--chaos specs), jaxpr hygiene (dispatch hot-path host-sync ban), the
limb-range abstract interpreter (uint32 overflow / representation
contract / LFp bound-algebra proofs + the MXU-readiness report), and
the SPMD soundness prover (collective legality / replication /
pad-absorption / donation discipline over the staged sharded
programs) — and prints a JSON report.  Exit status is 0 iff every
finding is covered by a justified waiver in ``analysis/waivers.toml``.

The first four families are pure AST + text: no jax import, no tracing,
seconds not minutes.  The ``range`` family traces every registered
field kernel through jax in interpret mode and dominates the wall time
(minutes on the Miller-loop kernels) — run families selectively with
``--only``.  The ``spmd`` family traces the sharded programs over an
AbstractMesh (~1s, cached).  The traced device-side checks (program
budget, zero-dim guard) live in the same package
(``analysis/jaxpr_lint.py``) but are driven by
``tools/dispatch_audit.py`` and the test suite.

Usage:
    JAX_PLATFORMS=cpu python tools/static_audit.py                 # whole repo
    JAX_PLATFORMS=cpu python tools/static_audit.py --quiet         # summary line only
    JAX_PLATFORMS=cpu python tools/static_audit.py --only lock,raise,registry,jaxpr
                                                      # fast AST tier
    JAX_PLATFORMS=cpu python tools/static_audit.py --only range    # kernel proofs only
    JAX_PLATFORMS=cpu python tools/static_audit.py --only spmd     # sharded-program proofs
    JAX_PLATFORMS=cpu python tools/static_audit.py --changed       # families scoped to
                                                      # the git diff vs HEAD
    JAX_PLATFORMS=cpu python tools/static_audit.py --write-range-report
                                                      # refresh RANGE_REPORT.json
    JAX_PLATFORMS=cpu python tools/static_audit.py --no-cache      # fresh range traces
                                                      # (skip .range_proof_cache.json)
    JAX_PLATFORMS=cpu python tools/static_audit.py --paths tests/fixtures/lint \\
        --config tests/fixtures/lint/lint.toml        # fixture corpus
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from lighthouse_tpu.analysis import (  # noqa: E402
    ALL_FAMILIES,
    AuditConfig,
    load_config,
    load_waivers,
    run_audit,
)

DEFAULT_WAIVERS = "lighthouse_tpu/analysis/waivers.toml"

# fast, pure-AST families: always worth running on any source change
AST_TIER = ("lock", "raise", "registry", "jaxpr")
# traced families, keyed by the source areas whose edits can change
# what they prove (mirrors the families' fingerprint dependency sets)
_RANGE_SCOPES = ("lighthouse_tpu/crypto/",)
_SPMD_SCOPES = (
    "lighthouse_tpu/parallel/",
    "lighthouse_tpu/crypto/bls/jax_backend/",
)
# edits here change the prover itself (or its harness): run everything
_ALL_SCOPES = ("lighthouse_tpu/analysis/", "tools/", "tests/fixtures/lint/")


def families_for_paths(paths):
    """Map changed repo-relative paths to the lint families to run.

    Empty iff no path warrants any family (e.g. a docs-only diff).
    Any ``.py`` change gets the AST tier; the traced families join when
    the diff touches their proof scope; analyzer/tooling edits escalate
    to every family.  Result preserves ALL_FAMILIES order.
    """
    fams: set = set()
    for p in paths:
        p = p.replace(os.sep, "/")
        if p.startswith(_ALL_SCOPES):
            return tuple(ALL_FAMILIES)
        if p.endswith(".py"):
            fams.update(AST_TIER)
        if p.startswith(_RANGE_SCOPES):
            fams.add("range")
        if p.startswith(_SPMD_SCOPES):
            fams.add("spmd")
    return tuple(f for f in ALL_FAMILIES if f in fams)


def _changed_paths(root):
    """Repo-relative paths changed vs HEAD (staged + unstaged +
    untracked), or None when git is unavailable."""
    import subprocess

    paths: set = set()
    for args in (
        ["git", "diff", "--name-only", "HEAD"],
        ["git", "ls-files", "--others", "--exclude-standard"],
    ):
        try:
            out = subprocess.run(
                args, cwd=root, capture_output=True, text=True, timeout=30
            )
        except (OSError, subprocess.TimeoutExpired):
            return None
        if out.returncode != 0:
            return None
        paths.update(p for p in out.stdout.splitlines() if p.strip())
    return sorted(paths)


def _record_history(result, history_path, scope="full", families=None,
                    changed=None):
    from lighthouse_tpu.utils import device_kind  # noqa: E402

    entry = {
        "kind": "static_audit",
        "device_kind": device_kind(),
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "pass": result.ok,
        "scope": scope,
        "files_scanned": result.files_scanned,
        "violations": len(result.violations),
        "waived": len(result.waived),
        "summary": result.summary(),
        "elapsed_s": round(result.elapsed_s, 3),
        "family_seconds": {
            k: round(v, 3) for k, v in result.family_seconds.items()
        },
    }
    if families is not None:
        entry["families"] = list(families)
    if changed is not None:
        entry["changed_files"] = len(changed)
    try:
        with open(history_path, "a") as f:
            f.write(json.dumps(entry) + "\n")
    except OSError:
        pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=ROOT,
                    help="audit root (default: the repo)")
    ap.add_argument("--paths", nargs="*", default=None,
                    help="override scan roots (files/dirs relative to "
                         "--root), e.g. a fixture corpus")
    ap.add_argument("--config", default=None,
                    help="audit config TOML (fixture corpora ship their "
                         "own lint.toml re-pointing the registries)")
    ap.add_argument("--waivers", default=None,
                    help=f"waiver file (default: {DEFAULT_WAIVERS} when "
                         f"auditing the repo, none otherwise)")
    ap.add_argument("--only", default=None, metavar="FAMILY[,FAMILY]",
                    help="run only these lint families (of: "
                         f"{', '.join(ALL_FAMILIES)}); implies no history "
                         f"row and, for a partial range run, no report "
                         f"drift check")
    ap.add_argument("--changed", action="store_true",
                    help="scope the family selection to the git diff vs "
                         "HEAD (staged + unstaged + untracked): AST tier "
                         "for any source change, range/spmd when their "
                         "proof scopes are touched, everything when the "
                         "analyzer itself changed; exits 0 immediately on "
                         "an empty or non-auditable diff")
    ap.add_argument("--list-families", action="store_true",
                    help="list the lint families and exit")
    ap.add_argument("--write-range-report", action="store_true",
                    help="regenerate the checked-in range report "
                         "(RANGE_REPORT.json) from the live kernels and "
                         "exit")
    ap.add_argument("--no-cache", action="store_true",
                    help="ignore and do not update the range proof cache "
                         "(.range_proof_cache.json); forces fresh kernel "
                         "traces")
    ap.add_argument("--quiet", action="store_true",
                    help="print only the verdict line, not the report")
    ap.add_argument("--no-history", action="store_true",
                    help="do not append an audit row to BENCH_HISTORY.jsonl")
    args = ap.parse_args(argv)

    if args.list_families:
        for fam in ALL_FAMILIES:
            print(fam)
        return 0

    if args.config is not None:
        cfg = load_config(args.config)
    else:
        cfg = AuditConfig()
    if args.no_cache:
        cfg.range_cache = False

    if args.write_range_report:
        from lighthouse_tpu.analysis import range_lint
        path = range_lint.write_report(args.root, cfg)
        print(f"wrote {path}")
        return 0

    changed = None
    if args.changed:
        if args.only is not None:
            ap.error("--changed and --only are mutually exclusive")
        changed = _changed_paths(args.root)
        if changed is None:
            print("static_audit: --changed could not read the git diff; "
                  "running the full audit", file=sys.stderr)
        else:
            fams = families_for_paths(changed)
            if not fams:
                print("static_audit: PASS (no auditable changes "
                      f"[{len(changed)} changed files])")
                return 0
            cfg.families = fams

    if args.only is not None:
        fams = tuple(f.strip() for f in args.only.split(",") if f.strip())
        unknown = [f for f in fams if f not in ALL_FAMILIES]
        if unknown:
            ap.error(f"unknown families: {', '.join(unknown)} "
                     f"(of: {', '.join(ALL_FAMILIES)})")
        cfg.families = fams
    if args.paths is not None:
        cfg.scan_roots = tuple(args.paths)
        # a custom corpus scans everything it contains
        cfg.lock_scan_include = tuple(
            p if p.endswith((".py", "/")) else p + "/" for p in args.paths
        )
        if args.config is None:
            cfg.exclude = ()  # explicit paths mean audit them, period

    waivers_path = args.waivers
    if waivers_path is None and args.config is None and args.paths is None:
        default = os.path.join(args.root, DEFAULT_WAIVERS)
        if os.path.exists(default):
            waivers_path = default
    waivers = load_waivers(waivers_path) if waivers_path else []

    result = run_audit(args.root, cfg, waivers)
    report = result.to_dict()
    if not args.quiet:
        print(json.dumps(report, indent=2))

    if (not args.no_history and args.config is None and args.paths is None
            and args.only is None):
        history = os.path.join(args.root, "BENCH_HISTORY.jsonl")
        if args.changed and changed is not None:
            _record_history(result, history, scope="changed",
                            families=cfg.families, changed=changed)
        else:
            _record_history(result, history)

    verdict = "PASS" if result.ok else "FAIL"
    counts = ", ".join(
        f"{k}={v}" for k, v in sorted(result.summary().items())
    ) or "clean"
    print(
        f"static_audit: {verdict} ({result.files_scanned} files, "
        f"{len(result.violations)} violations [{counts}], "
        f"{len(result.waived)} waived, {result.elapsed_s:.2f}s)",
        file=sys.stderr if args.quiet else sys.stdout,
    )
    return 0 if result.ok else 1


if __name__ == "__main__":
    sys.exit(main())
