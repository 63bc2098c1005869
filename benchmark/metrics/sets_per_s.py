"""Sets whose right verdict the client saw inside the window, per second."""


def read(ctx):
    done = sum(r.get("sets_ok", 0) for r in ctx.records
               if r.get("done") is not None and r["done"] <= ctx.end)
    return done / ctx.seconds
