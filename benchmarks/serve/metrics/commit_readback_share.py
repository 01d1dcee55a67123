"""Share of the traced window's wall time in spill readback on the host:
issuing the reads (``commit.issue``, whose window-overflow flushes decode
reads at once), draining them (``commit.drain``, with the tier's decode)
and writing the pages back into the cache (``commit.apply``: a host copy
of the whole buffer per page, and its upload).  Program spans."""

from servebench import program


def read(ctx):
    return program.share(program.record(), ctx, "commit.issue",
                         "commit.drain", "commit.apply")
