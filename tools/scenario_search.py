#!/usr/bin/env python3
"""Coverage-guided scenario search: hunt SLO violations, emit minimal specs.

Front-end for ``lighthouse_tpu.scenario.search``: seeds a mutation corpus
from registered scenarios, runs a budgeted deterministic search through
the real engine, delta-debugs every violation to a minimal reproducing
spec, and prints each one as a ready-to-paste ``SCENARIOS`` registry
entry.  Appends a ``scenario_search`` row (candidates run, violations
found, minimization steps) to BENCH_HISTORY.jsonl.

Exit status: 0 when the search completes with no violations, 3 when it
found at least one (the interesting outcome — a regression scenario to
register), non-zero argparse errors otherwise.

Continuous mode (``--budget-seconds``) trades the candidate budget for a
wall-clock one: sweeps keep launching under derived seeds until the
budget is spent, and every ddmin-minimized violation is auto-registered
as a JSON fixture in the committed regression corpus
(``tests/fixtures/scenarios/`` by default) where ``--scenario <name>``
replays it standalone.

Usage:
    JAX_PLATFORMS=cpu python tools/scenario_search.py --budget 32 --seed 7
    JAX_PLATFORMS=cpu python tools/scenario_search.py --corpus smoke --corpus long-non-finality
    JAX_PLATFORMS=cpu python tools/scenario_search.py --budget 8 --json /tmp/search.json
    JAX_PLATFORMS=cpu python tools/scenario_search.py --tracks device-faults --no-history
    JAX_PLATFORMS=cpu python tools/scenario_search.py --budget-seconds 60 --corpus smoke
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=7,
                    help="search RNG seed (the whole run is deterministic "
                         "under it)")
    ap.add_argument("--budget", type=int, default=32, metavar="N",
                    help="candidate engine runs (default 32)")
    ap.add_argument("--corpus", action="append", default=None,
                    metavar="NAME",
                    help="starting scenario (repeatable; default: smoke)")
    ap.add_argument("--tracks", action="append", default=None,
                    metavar="TRACK",
                    help="narrow the adversity mutation surface to these "
                         "tracks (repeatable; default: full surface)")
    ap.add_argument("--minimize-steps", type=int, default=24, metavar="N",
                    help="oracle budget per violation (0 disables "
                         "minimization)")
    ap.add_argument("--budget-seconds", type=float, default=None,
                    metavar="S",
                    help="continuous mode: run sweeps of --budget "
                         "candidates under derived seeds until S seconds "
                         "of wall clock are spent, registering minimized "
                         "violations into the regression corpus")
    ap.add_argument("--register-dir", metavar="DIR", default=None,
                    help="fixture corpus directory for continuous-mode "
                         "findings (default: tests/fixtures/scenarios)")
    ap.add_argument("--json", metavar="PATH",
                    help="write the full search result JSON to PATH")
    ap.add_argument("--no-history", action="store_true",
                    help="do not append a scenario_search row to "
                         "BENCH_HISTORY.jsonl")
    args = ap.parse_args(argv)

    from lighthouse_tpu.scenario.search import (
        SearchConfig,
        run_continuous,
        run_search,
    )

    if args.budget < 1:
        ap.error("--budget must be >= 1")
    if args.budget_seconds is not None and args.budget_seconds <= 0:
        ap.error("--budget-seconds must be > 0")
    config = SearchConfig(
        seed=args.seed,
        budget=args.budget,
        corpus=tuple(args.corpus or ("smoke",)),
        minimize_steps=args.minimize_steps,
        tracks=tuple(args.tracks) if args.tracks else None,
    )
    t0 = time.time()
    if args.budget_seconds is not None:
        result = run_continuous(
            config, args.budget_seconds, log=print,
            register_dir=args.register_dir,
        )
    else:
        result = run_search(config, log=print)
    elapsed = round(time.time() - t0, 3)
    out = result.to_dict()
    out["seed"] = args.seed
    out["elapsed_s"] = elapsed

    print(f"search seed={args.seed}: {result.candidates_run} candidates, "
          f"{len(result.violations)} violations, "
          f"{result.novel_fingerprints} novel fingerprints, "
          f"{result.minimization_steps} minimization steps, "
          f"{result.sweeps} sweeps, elapsed={elapsed}s")
    for v in result.violations:
        print(f"\nviolation: {v.spec.name} fails {list(v.failed)} "
              f"(fingerprint {v.fingerprint})")
        if v.registered:
            print(f"registered fixture: {v.registered}")
        if v.rendered:
            print("minimized registry entry (paste into "
                  "lighthouse_tpu/scenario/spec.py SCENARIOS):")
            print(v.rendered)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2, default=str)
    if not args.no_history:
        from lighthouse_tpu.utils import device_kind

        row = {
            "kind": "scenario_search",
            "device_kind": device_kind(),
            "measured_at": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
            ),
            "seed": args.seed,
            "budget": args.budget,
            "budget_seconds": args.budget_seconds,
            "sweeps": result.sweeps,
            "corpus": list(config.corpus),
            "candidates_run": result.candidates_run,
            "violations_found": len(result.violations),
            "novel_fingerprints": result.novel_fingerprints,
            "minimization_steps": result.minimization_steps,
            "elapsed_s": elapsed,
        }
        try:
            with open(os.path.join(ROOT, "BENCH_HISTORY.jsonl"), "a") as f:
                f.write(json.dumps(row) + "\n")
        except OSError:
            pass
    return 3 if result.violations else 0


if __name__ == "__main__":
    sys.exit(main())
