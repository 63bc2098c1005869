"""Edge key decode time per key: serve.decode span time over the keys
those spans decoded (serve/http.py, one span per POST), over the spans
that began before the profiler started.  None where the program has no
such span, or spans dropped."""


def read(ctx):
    spans = [s for s in ctx.spans
             if s["name"] == "serve.decode" and s["start"] < ctx.host_end]
    n = sum(s["fields"].get("keys", 0) for s in spans)
    if not n or ctx.spans_dropped:
        return None
    return sum(s["end"] - s["start"] for s in spans) * 1e3 / n
