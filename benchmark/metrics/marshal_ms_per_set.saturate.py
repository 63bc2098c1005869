"""Host marshal time per set: ingest.marshal span time over the sets
those spans marshalled (ingest/engine.py), over the spans that began
before the profiler started, for aggregate-and-proof sets with ~500-key
aggregates, in the closed-loop cell."""


def read(ctx):
    spans = [s for s in ctx.spans
             if s["name"] == "ingest.marshal" and s["start"] < ctx.host_end]
    n = sum(s["fields"].get("sets", 0) for s in spans)
    if not n or ctx.spans_dropped:
        return None
    return sum(s["end"] - s["start"] for s in spans) * 1e3 / n
