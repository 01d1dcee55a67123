"""Share of the traced window's wall time spent in
``ServeEngine._commit_pages``: the page pool, the tier's encode and the
spill readback, host side.  From host spans."""


def read(ctx):
    if not ctx.spans.count["engine.commit"]:
        return None
    return ctx.spans.total["engine.commit"] / ctx.window_s * 100
