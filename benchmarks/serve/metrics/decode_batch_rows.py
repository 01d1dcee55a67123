"""Cache rows per batched decode step: the rows that decoded (program
counter ``model.decode_rows``) over the scheduler's batched steps
(``model.decode_batches``, ``runtime.serving.SlotCache``), both over the
traced slice.  ``None`` where the program has no such counters or ran no
batched step."""

from servebench import program


def read(ctx):
    rec = program.record()
    if rec is None or not rec["counters"].get("model.decode_batches"):
        return None
    c = rec["counters"]
    return c.get("model.decode_rows", 0) / c["model.decode_batches"]
