"""Scheduler self time per tick: ``ServeScheduler.step`` wall time minus
the time in the ``ServeEngine`` calls it makes, per tick (host spans)."""


def read(ctx):
    t, n = ctx.spans.total, ctx.spans.count["sched.step"]
    if not n:
        return None
    inner = t["engine.prefill"] + t["engine.decode"] + t["engine.retire"]
    return (t["sched.step"] - inner) / n * 1e3
