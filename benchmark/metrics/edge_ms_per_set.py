"""POST-to-202 time at the client, summed over the submissions sent before
the profiler started, per set submitted: the edge's cost of a set."""


def read(ctx):
    recs = [r for r in ctx.records
            if r["status"] == 202 and r["post_start"] < ctx.host_end]
    n = sum(r["n_sets"] for r in recs)
    if not n:
        return None
    return sum((r["post_end"] - r["post_start"]) * 1e3 for r in recs) / n
