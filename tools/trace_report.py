#!/usr/bin/env python3
"""Stage-attribution report over a flight-recorder trace dump.

Reads a Chrome trace-event JSON file (a ``/trace`` scrape, a
breaker-open / scenario-SLO dump, or the checked-in fixture under
``tests/fixtures/trace/``) and prints per-stage latency attribution:
count / total / p50 / p99 per span name, the host-vs-device busy-time
split, pipeline overlap efficiency (wall / max(marshal, device) — 1.0
is perfect overlap, ~2.0 is fully serial), and any JIT compile events
with their per-program fingerprints.

``--check`` is the CI exit-code mode: the trace must parse, contain at
least one event, and attribute 100% of its wall time to known stages
(every event name registered in ``lighthouse_tpu.obs.SPANS``); exit 0
iff all three hold.

Usage:
    JAX_PLATFORMS=cpu python tools/trace_report.py /tmp/trace.json
    JAX_PLATFORMS=cpu python tools/trace_report.py --json /tmp/trace.json
    JAX_PLATFORMS=cpu python tools/trace_report.py --check tests/fixtures/trace/pipeline_trace.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def load_events(path: str) -> list:
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    events = doc.get("traceEvents") if isinstance(doc, dict) else doc
    if not isinstance(events, list):
        raise ValueError("no traceEvents array in trace file")
    for ev in events:
        if not isinstance(ev, dict) or "name" not in ev or "ts" not in ev:
            raise ValueError(f"malformed trace event: {ev!r}")
    return events


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="Chrome trace-event JSON file")
    ap.add_argument("--json", action="store_true",
                    help="emit the attribution report as JSON")
    ap.add_argument("--check", action="store_true",
                    help="CI mode: exit 0 iff the trace parses, is "
                         "non-empty, and every event name is a "
                         "registered span (100%% wall attribution)")
    args = ap.parse_args(argv)

    from lighthouse_tpu.obs import SPANS
    from lighthouse_tpu.obs import report as R

    try:
        events = load_events(args.trace)
    except (OSError, ValueError, json.JSONDecodeError) as exc:
        print(f"trace_report: unreadable trace {args.trace}: {exc}",
              file=sys.stderr)
        return 1

    unknown = R.unknown_names(events, SPANS)
    if args.check:
        if not events:
            print("trace_report: CHECK FAIL — empty trace", file=sys.stderr)
            return 1
        if unknown:
            print("trace_report: CHECK FAIL — events outside the span "
                  f"registry: {', '.join(unknown)}", file=sys.stderr)
            return 1
        print(f"trace_report: CHECK OK — {len(events)} events, "
              f"{len({ev['name'] for ev in events})} stages, all registered")
        return 0

    rep = R.attribution(events)
    if args.json:
        print(json.dumps(rep, indent=2, sort_keys=True))
        return 0

    print(f"trace: {args.trace}  ({rep['events']} events)")
    print(f"{'stage':24s} {'count':>7s} {'total_s':>10s} "
          f"{'p50_s':>10s} {'p99_s':>10s}")
    for name, st in rep["stages"].items():
        print(f"{name:24s} {st['count']:7d} {st['total_s']:10.4f} "
              f"{st['p50_s']:10.6f} {st['p99_s']:10.6f}")
    share = rep["share"]
    print(f"host/device busy: {share['host_s']:.4f}s / "
          f"{share['device_s']:.4f}s "
          f"({100 * share['host_share']:.1f}% / "
          f"{100 * share['device_share']:.1f}%)")
    ov = rep["overlap"]
    if ov["ratio"] is not None:
        print(f"overlap efficiency: {ov['ratio']:.3f} "
              f"(mode={ov['mode']}, wall={ov['wall_s']:.4f}s, "
              f"marshal={ov['marshal_s']:.4f}s, "
              f"device={ov['device_s']:.4f}s; 1.0 = perfect overlap)")
    for c in rep["compiles"]:
        print(f"jit.compile {c.get('fingerprint', '?'):14s} "
              f"{c['seconds']:.3f}s  {c.get('kernel', '')}")
    if unknown:
        print(f"WARNING: unregistered span names: {', '.join(unknown)}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
