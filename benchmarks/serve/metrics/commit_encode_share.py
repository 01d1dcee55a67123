"""Share of the traced window's wall time in the tier's encode of spilled
pages: KV transform, bit-plane pack, prescreen, LZ4 match and emit,
device calls included (program span ``tier.encode``,
``TierStore._encode_commit``)."""

from servebench import program


def read(ctx):
    return program.share(program.record(), ctx, "tier.encode")
