"""Median time from sending a POST to its 202 at the client, over the
submissions sent before the profiler started: JSON and hex parsing,
signature and key decode, admission and enqueue at the edge
(serve/http.py)."""

from benchmark import stats


def read(ctx):
    xs = [(r["post_end"] - r["post_start"]) * 1e3 for r in ctx.records
          if r["status"] == 202 and r["post_start"] < ctx.host_end]
    return stats.median(xs) if xs else None
