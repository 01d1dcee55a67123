"""Share of the traced window's wall time spent in ``ServeEngine.prefill``
(its page commit included), from host spans."""


def read(ctx):
    if not ctx.spans.count["engine.prefill"]:
        return None
    return ctx.spans.total["engine.prefill"] / ctx.window_s * 100
