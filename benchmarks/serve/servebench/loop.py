"""The closed loop that drives the served path, and the stamps it takes.

Every request goes in through ``ServeScheduler.submit`` and comes out
through ``ServeScheduler.step``, the path ``launch.serve`` uses.  After
each ``step()`` the loop reads the host clock once; every token that the
step produced is stamped with that time.  A client whose request retired
in the step submits its next one at once (no think time).

The loop also keeps, for the check, the program's logits at a fixed set
of vocabulary ids (``probe``): after each step an active request holds
the host logits its engine's decode returned, which pick its next token.
Copying a few hundred of them costs microseconds a tick.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Tracked:
    rid: int
    client: int
    index: int                # the client's n-th request; -1 = pre-roll
    prompt: np.ndarray
    n_out: int
    t_submit: float
    stamps: list = dataclasses.field(default_factory=list)
    tokens: np.ndarray | None = None      # (n_out,) once finished
    # probe[k - 1]: the program's logits at the probe ids that picked
    # output token k (k >= 1; token 0 comes from the prefill's logits)
    probe: list = dataclasses.field(default_factory=list)

    @property
    def finished(self) -> bool:
        return self.tokens is not None


class ClosedLoop:
    def __init__(self, sched, traffic, probe_ids, clock=time.perf_counter):
        self.sched = sched
        self.traffic = traffic
        self.probe_ids = probe_ids
        self.clock = clock
        self.live: dict[int, Tracked] = {}
        self.done: list[Tracked] = []
        self.ticks: list[float] = []          # end time of every step
        self._next = 0

    def _submit(self, req):
        from repro.runtime.serving import ServeRequest

        rid = self._next
        self._next += 1
        self.sched.submit([ServeRequest(
            req_id=rid, arrival=self.sched.clock, prompt=req.prompt,
            max_new_tokens=req.n_out, greedy=True, seed=rid)])
        self.live[rid] = Tracked(rid, req.client, req.index, req.prompt,
                                 req.n_out, self.clock())

    def start(self):
        """Every client sends its pre-roll request."""
        for c in range(self.traffic.clients):
            self._submit(self.traffic.preroll(c))

    def step(self) -> float:
        """One scheduler tick; returns its end time."""
        self.sched.step()
        t = self.clock()
        self.ticks.append(t)
        produced = {}
        for s in self.sched.active:
            if s is None:
                continue
            rid = s.req.req_id
            produced[rid] = len(s.out)
            if not s.done:
                self.live[rid].probe.append(s.logits[0, self.probe_ids])
        for rid in list(self.live):
            tr = self.live[rid]
            rec = self.sched.records[rid]
            n = rec.tokens.shape[1] if rec.finished else produced.get(rid, 0)
            tr.stamps.extend([t] * (n - len(tr.stamps)))
            if rec.finished:
                tr.tokens = np.asarray(rec.tokens).reshape(-1)
                del self.live[rid]
                self.done.append(tr)
                self._submit(self.traffic.next(tr.client))
        return t

    @property
    def warmed(self) -> bool:
        """Every client has finished its pre-roll and sent a request."""
        return all(tr.index >= 0 for tr in self.live.values()) \
            and len(self.live) == self.traffic.clients

    # -- what the window saw -------------------------------------------------
    def requests(self):
        yield from self.done
        yield from self.live.values()

    def end_to_end(self, t0: float, t1: float) -> dict:
        """tok/s, median TTFT and p99 inter-token gap over (t0, t1]."""
        inside = lambda t: t0 < t <= t1       # noqa: E731
        tokens, ttft, gaps = 0, [], []
        for tr in self.requests():
            if tr.index < 0:
                continue
            st = tr.stamps
            tokens += sum(map(inside, st))
            if st and inside(st[0]):
                ttft.append(st[0] - tr.t_submit)
            gaps.extend(b - a for a, b in zip(st, st[1:]) if inside(b))
        return {
            "tok_s": tokens / (t1 - t0),
            "ttft_p50_s": float(np.median(ttft)) if ttft else None,
            "itl_p99_s": float(np.percentile(gaps, 99)) if gaps else None,
            "tokens": tokens, "ttft_samples": len(ttft),
            "gap_samples": len(gaps),
        }

    def finished_in(self, t0: float, t1: float) -> list:
        return [tr for tr in self.done
                if tr.index >= 0 and t0 < tr.stamps[-1] <= t1]

    def attempted(self, t0: float, t1: float) -> list:
        """Requests in flight at some point of the window."""
        return [tr for tr in self.requests()
                if tr.index >= 0 and tr.t_submit <= t1
                and not (tr.finished and tr.stamps[-1] <= t0)]
