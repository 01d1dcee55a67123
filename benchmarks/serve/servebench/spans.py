"""Host spans around the calls into each layer, installed by the harness.

In a traced run :class:`Spans` wraps ``ServeScheduler.step`` and the
``ServeEngine`` calls (``prefill``, ``decode``, ``retire``,
``_commit_pages``) in a host timer and a ``jax.profiler.TraceAnnotation``
of the same name, so the spans land in the profiler's trace on the
device's clock.  Untraced runs install nothing.
"""

from __future__ import annotations

import collections
import functools
import time

SPANS = (
    ("runtime.serving", "ServeScheduler", "step", "sched.step"),
    ("runtime.serving", "ServeEngine", "prefill", "engine.prefill"),
    ("runtime.serving", "ServeEngine", "decode", "engine.decode"),
    ("runtime.serving", "ServeEngine", "retire", "engine.retire"),
    ("runtime.serving", "ServeEngine", "_commit_pages", "engine.commit"),
)
#: The spans of the engine calls that the scheduler makes; the device
#: programs in a trace are attributed to these.
CALLS = ("engine.prefill", "engine.decode", "engine.retire")


class Spans:
    """Wall time per span name, and the work each engine call did."""

    def __init__(self):
        self.total = collections.Counter()
        self.count = collections.Counter()
        self.calls = []          # (t0, t1, kind, start_pos, n_tokens)
        self.on = False          # accumulate only inside the window
        self._undo = []

    def install(self):
        import importlib

        from jax.profiler import TraceAnnotation

        for mod_name, cls_name, meth, span in SPANS:
            cls = getattr(importlib.import_module(f"repro.{mod_name}"), cls_name)
            orig = cls.__dict__[meth]
            setattr(cls, meth, self._wrap(orig, span, TraceAnnotation))
            self._undo.append((cls, meth, orig))

    def uninstall(self):
        for obj, name, orig in reversed(self._undo):
            setattr(obj, name, orig)
        self._undo.clear()

    def _wrap(self, fn, span, annotation):
        @functools.wraps(fn)
        def timed(obj, *a, **k):
            pos = getattr(obj, "pos", 0)
            t0 = time.perf_counter()
            with annotation(span):
                out = fn(obj, *a, **k)
            t1 = time.perf_counter()
            if self.on:
                self.total[span] += t1 - t0
                self.count[span] += 1
                if span == "engine.prefill":
                    self.calls.append((t0, t1, "prefill", pos, a[0].shape[1]))
                elif span == "engine.decode":
                    self.calls.append((t0, t1, "decode", pos, 1))
            return out
        return timed
