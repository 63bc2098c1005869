"""Client-side latency of the window's requests."""

from __future__ import annotations

GRACE_S = 60.0


def client_latencies_ms(ctx) -> list:
    """Milliseconds from each request's due time (open loop) or send time
    (closed loop) to the poll that saw its verdict.  A request that was
    refused, never answered or answered wrongly counts as having waited
    until the load generator gave up, a minute past the window."""
    out = []
    for r in ctx.records:
        t0 = r["due"] if r.get("due") is not None else r["post_start"]
        ok = (r["status"] == 202 and r.get("done") is not None
              and r.get("sets_ok") == r["n_sets"])
        t1 = r["done"] if ok else ctx.end + GRACE_S
        out.append((t1 - t0) * 1e3)
    return out
