"""Host bytes the page commit moves per output token, in MB (1e6 B):
device-to-host cache reads, host copies of the cache and uploads back
(program counters ``commit.d2h_bytes``, ``commit.copy_bytes``,
``commit.h2d_bytes``) over the tokens sampled (``sched.tokens``), both
over the traced slice."""

from servebench import program

COUNTERS = ("commit.d2h_bytes", "commit.copy_bytes", "commit.h2d_bytes")


def read(ctx):
    rec = program.record()
    if rec is None or not rec["counters"].get("sched.tokens"):
        return None
    c = rec["counters"]
    return sum(c.get(n, 0) for n in COUNTERS) / c["sched.tokens"] / 1e6
