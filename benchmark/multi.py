#!/usr/bin/env python3
"""Several windows of one cell in one process, set up once.

    python3 benchmark/multi.py --workload gossip-hot-steady \\
        --seeds 11,12,13 --seconds 30 [--control unweighted] [--rates 300,600]

Not part of a benchmark run.  It reads what a limit or a rate is set
from: the compared numbers of a dozen seeds (the lower reading), the same
with the control switched on (the upper reading), or, with ``--rates``,
the open-loop rate sweep that finds the knee.  Each window prints one
JSON line: the seed, the offered rate, the end-to-end numbers, the
generator's lateness and every compared number.

``--control unweighted`` gives every set the weight 1 in the device
batch (``IngestEngine._weights``): the step that would save the weights'
scalar multiplications and breaks the guarantee that a batch accepts only
when every set in it is valid.  The cancelling pair of poisoned sets is
what catches it.
"""

from __future__ import annotations

import argparse
import multiprocessing
import os
import sys
import time
from concurrent.futures import ProcessPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from benchmark import correctness, run as R, stats, traffic as T  # noqa: E402


def unweighted() -> None:
    from lighthouse_tpu.ingest.engine import IngestEngine

    IngestEngine._weights = staticmethod(
        lambda weights, n, reps: [1] * (n + reps))


def window(system, cell, seed, seconds, pool, device):
    tr = T.build(cell.config, cell.mix, seed, seconds, mapper=pool.map)
    warm_wrong = R.key_warmup(system, tr)
    win = R.drive(system, tr, seconds, False, device)
    res = correctness.judge(tr, win, seed, warm_wrong, pool.map)
    ctx = R.context(cell, seconds, 0.0, win, res.sets_ok)
    metrics = {m["name"]: R.load_reader(m["name"], cell.root)(ctx)
               for m in cell.end_to_end if m["name"] != "setup_s"}
    lat = [r["post_start"] - r["due"] for r in win.records if r.get("due")]
    late = [r for r in win.records if r.get("done") and r["done"] > win.end]
    return {"seed": seed, "rate": cell.mix.get("rate_sets_per_s"),
            "correct": res.correct, "attempted": res.attempted,
            "failed": res.failed, "metrics": metrics,
            "sets_sent": sum(r["n_sets"] for r in win.records),
            "done_after_window": len(late),
            "lateness_ms_p50": stats.median(lat) * 1e3 if lat else None,
            "lateness_ms_max": max(lat) * 1e3 if lat else None,
            "gets_per_s": win.loadgen["gets_per_s"],
            "checks": {k: v["value"] for k, v in res.checks.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated; with --rates, one per rate")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", choices=("unweighted",), default=None)
    ap.add_argument("--rates", default=None,
                    help="comma-separated offered rates (sets/s), open loop")
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates else None
    R.prepare_env()
    t0 = time.monotonic()
    info, device = R.find_devices(cell.chips)
    if args.control == "unweighted":
        unweighted()
    system = R.boot(cell.config, cell.mix["warm_sizes"])
    R.emit({"boot_s": time.monotonic() - t0, "device": info,
            "control": args.control, **system.setup})
    ctx_mp = multiprocessing.get_context("spawn")
    try:
        with ProcessPoolExecutor(max(1, min(8, (os.cpu_count() or 2) - 2)),
                                 mp_context=ctx_mp) as pool:
            for k, seed in enumerate(seeds):
                if rates is not None:
                    cell.mix["rate_sets_per_s"] = rates[k]
                R.emit(window(system, cell, seed, args.seconds, pool, device))
    finally:
        system.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
