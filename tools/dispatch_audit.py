#!/usr/bin/env python3
"""Static dispatch audit: count distinct Pallas programs per backend config.

The chains+miller composition failed on hardware not because any kernel
was wrong but because the OLD chain design asked Mosaic to compile ~21
chain-segment programs plus ~24 Fermat window variants alongside the
fused Miller programs — a >6,700 s pathological compile (session2
06:52Z).  The megachain consolidation (pallas_fp.py) makes the program
count a budgeted, auditable quantity: this tool traces the exact device
kernel each config would run (`jax.make_jaxpr` — trace only, nothing is
Mosaic-compiled), walks the jaxpr for `pallas_call` equations, and
fingerprints each by (kernel name & source line, operand avals, grid).

Two numbers per config:

* ``programs`` — distinct fingerprints ≈ distinct Mosaic compiles the
  config pays on first run (the compile-time axis).
* ``calls`` — static ``pallas_call`` equation count ≈ stacked dispatches
  per batch (the dispatch-overhead axis).  A pallas_call under a
  ``lax.scan``/``fori_loop`` counts once here even though it dispatches
  per iteration: this is the *static* composition, which is exactly what
  Mosaic compile cost scales with.

Budget enforcement (the acceptance criterion): any config with chains
enabled must stage at most ``--budget`` (default 6) distinct megachain
programs.  Violations — or a watchdog timeout while tracing a
budget-critical config — exit nonzero.

Usage:
    JAX_PLATFORMS=cpu python tools/dispatch_audit.py            # default matrix
    JAX_PLATFORMS=cpu python tools/dispatch_audit.py --quick    # budget-critical only
    JAX_PLATFORMS=cpu python tools/dispatch_audit.py --full     # + slow stacked-op trace
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


# ---------------------------------------------------------------------------
# Config matrix
# ---------------------------------------------------------------------------

# (name, pallas, chains, miller, wsm, device_h2c, budget_critical)
MATRIX = [
    # default TPU composition today: pallas + fused miller
    ("pallas+miller", True, False, True, False, False, False),
    # full fused stack without chains
    ("pallas+miller+wsm", True, False, True, True, False, False),
    # THE composition the budget exists for: chains + fused miller
    ("pallas+chains+miller", True, True, True, False, False, True),
    # same with device h2c — the sqrt chains live here
    ("pallas+chains+miller+h2c", True, True, True, False, True, True),
]

# per-op stacked path (no fusion): thousands of pallas_call eqns, each
# re-tracing the Montgomery kernel — minutes of trace time on one core,
# so opt-in via --full
SLOW_MATRIX = [
    ("pallas", True, False, False, False, False, False),
]


class TraceTimeout(Exception):
    pass


def _alarm(_sig, _frm):
    raise TraceTimeout()


# ---------------------------------------------------------------------------
# Jaxpr walk — shared with the static analyzer (analysis/jaxpr_lint.py is
# the single home of the walk, the fingerprints, and the budget default;
# this tool is the tracing front-end)
# ---------------------------------------------------------------------------

from lighthouse_tpu.analysis.jaxpr_lint import (  # noqa: E402
    DEFAULT_CHAIN_BUDGET,
    audit_jaxpr,
    is_chain_program as _is_chain_program,
)


# ---------------------------------------------------------------------------
# Per-config trace
# ---------------------------------------------------------------------------


def _build_signature_sets(n: int):
    from lighthouse_tpu.crypto.bls.api import SecretKey, SignatureSet

    sets = []
    for i in range(n):
        sk = SecretKey(200 + i)
        msg = bytes([i % 256]) * 32
        sets.append(SignatureSet(sk.sign(msg), [sk.public_key()], msg))
    return sets


def trace_config(name, pallas, chains, miller, wsm, device_h2c, sets,
                 timeout_s):
    import jax

    from lighthouse_tpu.crypto.bls.jax_backend import backend as B
    from lighthouse_tpu.crypto.bls.jax_backend import fp as F

    F.set_force_device_paths(True)
    F.set_pallas(pallas)
    F.set_chains(chains)
    F.set_miller(miller)
    F.set_wsm(wsm)

    bk = B.JaxBackend(min_batch=8, device_h2c=device_h2c)
    mb = bk.marshal_sets(sets)
    if mb.invalid:
        raise RuntimeError("marshal of synthetic sets failed")
    fn = B._verify_kernel_h2c if device_h2c else B._verify_kernel

    old = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(timeout_s)
    t0 = time.perf_counter()
    try:
        closed = jax.make_jaxpr(fn)(*mb.args)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
        F.set_force_device_paths(False)
    trace_s = time.perf_counter() - t0

    programs, n_calls = audit_jaxpr(closed)
    # distinct chain PROGRAMS = distinct full fingerprints: two chains of
    # different digit count share the kernel def line but lower to
    # different Mosaic programs (the tape aval differs)
    chain_fps = [fp for fp in programs if _is_chain_program(fp)]
    return {
        "config": name,
        "programs": len(programs),
        "calls": n_calls,
        "chain_programs": len(chain_fps),
        "chain_kernels": sorted({fp[0] for fp in chain_fps}),
        "trace_seconds": round(trace_s, 2),
    }


# ---------------------------------------------------------------------------
# Reporting
# ---------------------------------------------------------------------------


def _record_history(rows, budget, ok):
    path = os.path.join(ROOT, "BENCH_HISTORY.jsonl")
    entry = {
        "kind": "dispatch_audit",
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "budget_chain_programs": budget,
        "pass": ok,
        "configs": rows,
    }
    try:
        with open(path, "a") as f:
            f.write(json.dumps(entry) + "\n")
    except OSError:
        pass


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sets", type=int, default=2,
                    help="synthetic signature sets per batch (padded to 8)")
    ap.add_argument("--budget", type=int, default=DEFAULT_CHAIN_BUDGET,
                    help="max distinct chain programs per composition")
    ap.add_argument("--timeout", type=int, default=900,
                    help="per-config trace watchdog seconds")
    ap.add_argument("--quick", action="store_true",
                    help="budget-critical configs only")
    ap.add_argument("--full", action="store_true",
                    help="also trace the slow per-op stacked path")
    ap.add_argument("--no-history", action="store_true",
                    help="do not append an audit row to BENCH_HISTORY.jsonl")
    args = ap.parse_args()

    matrix = list(MATRIX)
    if args.quick:
        matrix = [c for c in matrix if c[6]]
    if args.full:
        matrix += SLOW_MATRIX

    from lighthouse_tpu.utils import metrics as M

    sets = _build_signature_sets(args.sets)
    rows, ok = [], True
    for name, pallas, chains, miller, wsm, h2c, critical in matrix:
        try:
            row = trace_config(name, pallas, chains, miller, wsm, h2c,
                               sets, args.timeout)
        except TraceTimeout:
            row = {"config": name, "timeout": True,
                   "timeout_seconds": args.timeout}
            if critical:
                ok = False
            rows.append(row)
            print(json.dumps(row), flush=True)
            continue
        M.DISPATCH_PROGRAMS.set(row["programs"], (name,))
        M.DISPATCH_CALLS.set(row["calls"], (name,))
        if chains and row["chain_programs"] > args.budget:
            row["budget_violation"] = True
            ok = False
        rows.append(row)
        print(json.dumps(row), flush=True)

    if not args.no_history:
        _record_history(rows, args.budget, ok)

    verdict = "PASS" if ok else "FAIL"
    print(f"dispatch_audit: {verdict} "
          f"(budget: <= {args.budget} chain programs per composition)")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
