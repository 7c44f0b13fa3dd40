"""Mean wall time of one engine step in the window: the `bench.step`
host spans around `server.step()` that ended in it (host clock)."""


def read(ctx):
    rec = ctx["rec"]
    d = [t1 - t0 for t0, t1, _, _ in rec.steps
         if rec.t0 <= t1 < rec.t0 + ctx["seconds"]]
    if not d:
        return None
    return 1e3 * sum(d) / len(d)
