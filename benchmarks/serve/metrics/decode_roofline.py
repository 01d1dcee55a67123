"""The decode step's share of its roofline, for the cells that the
entry ``decode_step_roofline`` does not list: the same reading, by that
metric's reader, so the decode step is counted one way in every cell."""


def read(ctx):
    return ctx.cell.reader("decode_step_roofline")(ctx)
