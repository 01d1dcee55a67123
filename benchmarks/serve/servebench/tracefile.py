"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

One pass over the trace gives, on the device's clock and inside the
window that the harness marked with a ``bench.window`` annotation:

* ``busy_s``: the union of the intervals in which an XLA op ran, over
  the TPU planes, averaged over the chips;
* ``modules``: every program execution (``XLA Modules`` line) with the
  harness call span (``engine.prefill``, ``engine.decode``, ...) that was
  open on the host at its midpoint;
* ``kernels``: every Pallas kernel (``tpu_custom_call`` op) with its
  device time and the bytes of its operands and results, read from the
  op's HLO signature;
* ``idle_gaps``: the device's idle time, summed by the innermost
  harness span open on the host in the middle of each gap.

Host spans and device events sit on one clock only to within a fraction
of a millisecond, so a program is attributed by the midpoint of its
execution, and a program (one compiled executable, one fingerprint) by
where most of its executions ran.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re

WINDOW = "bench.window"
OUTSIDE = "outside spans"
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
                "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_ARRAY = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")


def hlo_bytes(signature: str) -> int:
    """Bytes of every array shape written in an HLO op's text: the
    results before ``custom-call(`` and the operands inside it."""
    head = signature.split("custom_call_target", 1)[0]
    total = 0
    for dt, dims in _ARRAY.findall(head):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def kernel_name(op: str) -> str:
    """``%pack_planes_pallas.1 = u8[...] custom-call(...)`` -> the name."""
    return op.split(" = ")[0].lstrip("%").split(".")[0]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    chips: int
    modules: list          # (program, fingerprint, span, start_ns, duration_ns)
    kernels: list          # (name, span, duration_ns, bytes)
    idle_gaps: dict        # host span -> idle seconds

    def program_span(self) -> dict:
        """(program, fingerprint) -> the call span most of its executions
        ran under."""
        votes = collections.defaultdict(collections.Counter)
        for n, f, s, _, _ in self.modules:
            votes[(n, f)][s] += 1
        return {k: c.most_common(1)[0][0] for k, c in votes.items()}

    def module_time(self, program: str, span: str) -> list:
        """Durations (s) of every execution of the programs named
        ``program`` that run mostly under the call span ``span``."""
        owner = self.program_span()
        return [d / 1e9 for n, f, _, _, d in self.modules
                if n == program and owner[(n, f)] == span]

    def device_ops(self, top: int = 10) -> list:
        """The programs that took the most device time, by name and the
        call span they ran under."""
        acc = collections.Counter()
        for n, _, s, _, d in self.modules:
            acc[f"{n} [{s}]"] += d / 1e9
        return [[k, v] for k, v in acc.most_common(top)]

    def gaps(self, top: int = 10) -> list:
        return [[k, v] for k, v in
                sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:top]]


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


class _HostSpans:
    """The innermost harness span open at a time, from the host's lines."""

    def __init__(self, events):
        by = collections.defaultdict(list)       # name -> [(start, end)]
        for name, s, e in events:
            by[name].append((s, e))
        self.by = {k: sorted(v) for k, v in by.items()}
        self.starts = {k: [s for s, _ in v] for k, v in self.by.items()}

    def at(self, t: float, among=None) -> str:
        """Innermost span open at ``t``, of the names ``among`` if given;
        without one, of any name."""
        best, best_start = OUTSIDE, None
        for name, ivs in self.by.items():
            if among is not None and name not in among:
                continue
            i = bisect.bisect_right(self.starts[name], t) - 1
            if i >= 0 and t < ivs[i][1] and (best_start is None
                                              or ivs[i][0] > best_start):
                best, best_start = name, ivs[i][0]
        return best

    def call_at(self, t: float, calls) -> str:
        """The call span open at ``t``; else the innermost span."""
        s = self.at(t, calls)
        return s if s != OUTSIDE else self.at(t)


def reduce(path: str, span_names, call_spans=()) -> Reduced:
    """Read the trace at ``path``.  ``span_names`` are the harness's
    spans; ``call_spans`` those of them that wrap a call which runs
    programs on the device, and by which programs are attributed."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    wanted = set(span_names) | {WINDOW}
    host = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
        elif plane.name.startswith("/device:TPU:"):
            devices.append({ln.name: ln for ln in plane.lines})
    win = [h for h in host if h[0] == WINDOW]
    if not win or not devices:
        raise ValueError("trace holds no marked window or no TPU plane")
    w0, w1 = win[0][1], win[0][2]
    spans = _HostSpans([h for h in host if h[0] != WINDOW])
    calls = set(call_spans)

    busy = 0.0
    modules, kernels = [], []
    gaps = collections.Counter()
    for lines in devices:
        ivs = []
        for e in lines["XLA Ops"].events if "XLA Ops" in lines else ():
            s, t = e.start_ns, e.start_ns + e.duration_ns
            if t <= w0 or s >= w1:
                continue
            ivs.append((max(s, w0), min(t, w1)))
            if "tpu_custom_call" in e.name:
                kernels.append((kernel_name(e.name),
                                spans.call_at((s + t) / 2, calls),
                                e.duration_ns, hlo_bytes(e.name)))
        merged = _union(ivs)
        busy += sum(e - s for s, e in merged) / 1e9
        edge = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edge[0::2], edge[1::2]):
            if b > a:
                gaps[spans.at((a + b) / 2)] += (b - a) / 1e9
        for e in lines["XLA Modules"].events if "XLA Modules" in lines else ():
            if w0 <= e.start_ns < w1:
                name, _, fp = e.name.partition("(")
                mid = e.start_ns + e.duration_ns / 2
                modules.append((name, fp.rstrip(")"), spans.call_at(mid, calls),
                                e.start_ns, e.duration_ns))
    n = len(devices)
    return Reduced(window_s=(w1 - w0) / 1e9, busy_s=busy / n, chips=n,
                   modules=modules, kernels=kernels,
                   idle_gaps={k: v / n for k, v in gaps.items()})
