"""Spans and counters of the served path, on the profiler's clock.

A span marks one piece of host work where it happens::

    with obs.span("commit.pull"):
        buf = np.asarray(layers["k"])

Recording is off by default; then :func:`span` is one flag test that
returns a shared no-op context.  It is on between :func:`enable` and
:func:`disable`, and while a ``jax.profiler`` trace runs: every scheduler
tick (:func:`tick`) looks at the profiler, and a trace that has started
since the last tick turns recording on over a fresh record (one that has
stopped turns it off again).  While on, a span opens a
``jax.profiler.TraceAnnotation`` of its name, so it lands in the trace on
the profiler's clock beside the device's ops, carrying its request
(``req``, inherited from the enclosing span) as metadata; and it adds to
its name's count, inclusive ``perf_counter`` time and self time
(inclusive minus what its child spans cover).

Counters (:func:`count`) are always on.  :func:`snapshot` returns spans
and counters as plain dicts; the profiler's trace is the only record of
individual spans.
"""

from __future__ import annotations

import collections
import time

from jax.profiler import StepTraceAnnotation, TraceAnnotation

#: Every span name the program opens.  None is a name the benchmark's own
#: spans use (``servebench/spans.py``): two nested intervals of one name
#: would make the innermost-span lookup of a trace ambiguous.
SPAN_NAMES = (
    "sched.tick", "sched.admit", "sched.engine_init", "sched.sample",
    "sched.ratio",
    "model.prefill", "model.decode", "model.sync",
    "commit.pages", "commit.pull", "commit.append", "commit.issue",
    "commit.drain", "commit.apply",
    "tier.encode", "tier.kv_transform", "tier.pack", "tier.prescreen",
    "tier.lz4_match", "tier.lz4_emit", "tier.decode", "tier.gather",
    "pool.release", "pool.reclaim",
)
#: The fixed counter names; ``kernel.<kernel>.<path>`` counts each
#: backend decision of ``repro.kernels.dispatch.choose`` besides.
#: ``tier.kv_host_windows`` counts KV windows transformed on the host.
#: ``model.prefill_chunked`` counts the prefills whose attention scans
#: query chunks (``models.layers.query_chunk``).  ``model.decode_batches``
#: counts the scheduler's batched decode steps (``SlotCache``) and
#: ``model.decode_rows`` the cache rows that decoded in them.
COUNTER_NAMES = ("commit.d2h_bytes", "commit.copy_bytes", "commit.h2d_bytes",
                 "sched.tokens", "tier.kv_host_windows",
                 "model.prefill_chunked", "model.decode_batches",
                 "model.decode_rows")

_DECLARED = frozenset(SPAN_NAMES)


class _Recorder:
    def __init__(self):
        self.on = False        # spans record
        self.forced = False    # enable() was called
        self.traced = False    # a profiler trace ran at the last tick
        self.stack = []        # per open span: [time its children cover, req]
        self.spans = {}        # name -> [count, inclusive s, self s]
        self.counters = collections.Counter()


_R = _Recorder()


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


class _Span:
    __slots__ = ("name", "ann", "req", "t0")

    def __init__(self, name: str, ann, req):
        self.name, self.ann, self.req = name, ann, req

    def __enter__(self):
        self.ann.__enter__()
        _R.stack.append([0.0, self.req])
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self.ann.__exit__(*exc)
        children, _ = _R.stack.pop()
        if _R.stack:
            _R.stack[-1][0] += dt
        rec = _R.spans.setdefault(self.name, [0, 0.0, 0.0])
        rec[0] += 1
        rec[1] += dt
        rec[2] += dt - children
        return False


def span(name: str, req: str | None = None):
    """Context for one piece of host work named ``name`` (one of
    :data:`SPAN_NAMES`), on behalf of request ``req`` (``"r7"``), or of
    the enclosing span's request when ``None``."""
    if not _R.on:
        return _NULL
    if name not in _DECLARED:
        raise ValueError(f"span {name!r} is not in obs.SPAN_NAMES")
    if req is None and _R.stack:
        req = _R.stack[-1][1]
    ann = TraceAnnotation(name, req=req) if req else TraceAnnotation(name)
    return _Span(name, ann, req)


def tick(n: int):
    """The span of scheduler tick ``n`` (``sched.tick``), a
    ``StepTraceAnnotation`` numbered ``n`` in the profiler's trace.  Also
    where recording follows the profiler: a trace started since the last
    tick turns it on over a fresh record; one stopped turns it off."""
    traced = TraceAnnotation.is_enabled()
    if traced != _R.traced:
        _R.traced = traced
        if traced:
            reset()
        _R.on = _R.forced or traced
    if not _R.on:
        return _NULL
    return _Span("sched.tick",
                 StepTraceAnnotation("sched.tick", step_num=n), None)


def count(name: str, n: int = 1):
    """Add ``n`` to the counter ``name``."""
    _R.counters[name] += n


def enable():
    """Record spans until :func:`disable`, trace or no trace."""
    _R.forced = _R.on = True


def disable():
    """Stop recording, unless a profiler trace keeps it on."""
    _R.forced = False
    _R.on = _R.traced


def reset():
    """Forget every span total and counter (open spans still close)."""
    _R.spans = {}
    _R.counters = collections.Counter()


def snapshot() -> dict:
    """``{"spans": {name: {"count", "total_s", "self_s"}}, "counters":
    {name: n}}`` since the last :func:`reset`."""
    return {
        "spans": {k: {"count": c, "total_s": t, "self_s": s}
                  for k, (c, t, s) in _R.spans.items()},
        "counters": dict(_R.counters),
    }
