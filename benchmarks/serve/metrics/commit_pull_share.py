"""Share of the traced window's wall time in which the page commit reads
whole K and V caches from the device to the host (program span
``commit.pull``, in ``ServeEngine._commit_pages``).  The read waits for
the step that wrote the caches, so it holds that wait too."""

from servebench import program


def read(ctx):
    return program.share(program.record(), ctx, "commit.pull")
