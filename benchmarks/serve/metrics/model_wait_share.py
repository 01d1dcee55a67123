"""Share of the traced window's wall time in which the host waits for a
model step's logits (program span ``model.sync``, the host read after
``ServeEngine.prefill``/``decode``).  At a page boundary the wait falls
in ``commit.pull`` instead, which reads the caches first."""

from servebench import program


def read(ctx):
    return program.share(program.record(), ctx, "model.sync")
