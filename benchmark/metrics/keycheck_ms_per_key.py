"""Device key validation time per key: serve.keycheck span time (one
span per flush that carries unchecked keys, held until the device's
answer is on the host; serve/service.py) over the keys those spans
checked, over the spans that began before the profiler started.  None
where the program has no such span, or spans dropped."""


def read(ctx):
    spans = [s for s in ctx.spans
             if s["name"] == "serve.keycheck" and s["start"] < ctx.host_end]
    n = sum(s["fields"].get("keys", 0) for s in spans)
    if not n or ctx.spans_dropped:
        return None
    return sum(s["end"] - s["start"] for s in spans) * 1e3 / n
