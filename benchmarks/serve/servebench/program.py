"""The served program's own spans and counters (``repro.obs``), as the
per-layer readers of a traced run take them.

The program records while a ``jax.profiler`` trace runs: its first
scheduler tick after the trace starts opens a fresh record, so after a
traced run the record covers the ticks of the traced slice, the same
ticks as the harness's spans (``spans.py``).  A program without the
recorder, or one that recorded nothing, gives ``None``.
"""

from __future__ import annotations


def record():
    """``{"spans": {name: {"count", "total_s", "self_s"}}, "counters":
    {name: n}}``, or ``None``."""
    try:
        from repro import obs
    except ImportError:
        return None
    snap = obs.snapshot()
    return snap if snap["spans"] else None


def share(rec, ctx, *names):
    """Percent of the traced slice (``ctx.window_s``) spent in the spans
    ``names``, inclusive of their children; ``None`` without a record."""
    if rec is None:
        return None
    total = sum(rec["spans"].get(n, {}).get("total_s", 0.0) for n in names)
    return total / ctx.window_s * 100
