#!/usr/bin/env python3
"""Run a named adversarial scenario and gate it on its SLOs.

Front-end for ``lighthouse_tpu.scenario``: resolves a scenario from the
``SCENARIOS`` registry (``--list`` shows them), runs the engine, prints
each SLO verdict, optionally writes the full JSON report, and appends a
``scenario`` row to BENCH_HISTORY.jsonl.  Exit status is 0 iff every SLO
assertion passed.

Reproduction: the report records the seed and the fired-fault sequence;
re-running the same name with the same seed replays the identical run
(the fingerprint line must match).

Usage:
    JAX_PLATFORMS=cpu python tools/scenario_run.py --list
    JAX_PLATFORMS=cpu python tools/scenario_run.py --scenario smoke
    JAX_PLATFORMS=cpu python tools/scenario_run.py --scenario mainnet-shape --json /tmp/r.json
    JAX_PLATFORMS=cpu python tools/scenario_run.py --scenario mainnet-shape:seed=99 --no-history
    JAX_PLATFORMS=cpu python tools/scenario_run.py --scenario slashing-flood --repeat 3
    JAX_PLATFORMS=cpu python tools/scenario_run.py --scenario long-non-finality --repeat 2
    JAX_PLATFORMS=cpu python tools/scenario_run.py --scenario hostile-checkpoint-sync
    JAX_PLATFORMS=cpu python tools/scenario_run.py --scenario registry-pressure
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scenario", metavar="NAME[:seed=N]",
                    help="scenario to run (see --list); an optional "
                         ":seed=N override reruns it under another seed")
    ap.add_argument("--list", action="store_true",
                    help="list registered scenarios and exit")
    ap.add_argument("--json", metavar="PATH",
                    help="write the full JSON report to PATH")
    ap.add_argument("--repeat", type=int, default=1, metavar="N",
                    help="run the scenario N times and fail (exit 2) if "
                         "the run fingerprints diverge — the determinism "
                         "gate behind every regression scenario")
    ap.add_argument("--no-history", action="store_true",
                    help="do not append a scenario row to BENCH_HISTORY.jsonl")
    args = ap.parse_args(argv)

    from lighthouse_tpu.scenario import SCENARIOS, parse_scenario_arg
    from lighthouse_tpu.scenario.engine import ScenarioEngine

    if args.list:
        for name, spec in SCENARIOS.items():
            print(f"{name:24s} seed={spec.seed} nodes={spec.n_nodes} "
                  f"epochs={spec.epochs} traffic={','.join(spec.traffic)} "
                  f"adversity={len(spec.adversity)} tracks")
        return 0
    if not args.scenario:
        ap.error("--scenario NAME required (or --list)")

    spec = parse_scenario_arg(args.scenario)
    history = None if args.no_history else os.path.join(
        ROOT, "BENCH_HISTORY.jsonl"
    )
    if args.repeat < 1:
        ap.error("--repeat must be >= 1")
    reports = []
    for i in range(args.repeat):
        # one history row and one JSON report per invocation (the last
        # run), however many determinism repeats were asked for
        last = i == args.repeat - 1
        reports.append(ScenarioEngine(
            spec,
            out_path=args.json if last else None,
            history_path=history if last else None,
        ).run())
    report = reports[-1]

    for s in report["slo"]:
        if s["ok"]:
            verdict = "ok  "
        elif s.get("level") == "warn":
            verdict = "WARN"
        else:
            verdict = "FAIL"
        detail = f"  ({s['detail']})" if s["detail"] and not s["ok"] else ""
        print(f"  {verdict} {s['name']:22s} {s['observed']} "
              f"(threshold {s['threshold']}){detail}")
    if report.get("trace_dump"):
        print(f"  trace dump: {report['trace_dump']}")
    verdict = "PASS" if report["pass"] else "FAIL"
    print(f"scenario {report['scenario']}: {verdict}  "
          f"seed={report['seed']} fingerprint={report['fingerprint']} "
          f"slots={report['slots']} faults={len(report['fired_faults'])} "
          f"elapsed={report['elapsed_s']}s")
    if args.repeat > 1:
        fps = [r["fingerprint"] for r in reports]
        if len(set(fps)) > 1:
            print(f"FINGERPRINT DIVERGENCE over {args.repeat} runs: {fps}")
            return 2
        print(f"fingerprint stable over {args.repeat} runs: {fps[0]}")
        # the fingerprint only covers fault/head/finality history; the
        # per-epoch SLO snapshots must replay identically too, and a
        # divergence names the first epoch that drifted
        divergent = _first_divergent_epoch(reports)
        if divergent is not None:
            print(f"EPOCH SLO DIVERGENCE over {args.repeat} runs: "
                  f"first divergent epoch {divergent}")
            return 2
        n_epochs = len(reports[0].get("epochs") or ())
        if n_epochs:
            print(f"per-epoch SLO snapshots stable over {args.repeat} "
                  f"runs: {n_epochs} epochs")
    return 0 if all(r["pass"] for r in reports) else 1


def _epoch_signature(report: dict) -> list:
    """Comparable per-epoch digest: (epoch, gate verdicts, facts).
    Tolerant of reports without epoch records (older engines, stubs)."""
    out = []
    for rec in report.get("epochs") or ():
        gates = tuple(
            (g.get("name"), bool(g.get("ok")))
            for g in rec.get("slo") or ()
        )
        facts = tuple(sorted((rec.get("facts") or {}).items()))
        out.append((rec.get("epoch"), gates, facts))
    return out


def _first_divergent_epoch(reports: list) -> int | None:
    """First epoch whose SLO snapshot differs from run 1's, or None."""
    base = _epoch_signature(reports[0])
    for rep in reports[1:]:
        sig = _epoch_signature(rep)
        for a, b in zip(base, sig):
            if a != b:
                return a[0]
        if len(base) != len(sig):
            longer = base if len(base) > len(sig) else sig
            return longer[min(len(base), len(sig))][0]
    return None


if __name__ == "__main__":
    sys.exit(main())
